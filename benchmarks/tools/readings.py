"""Readings that the comparison's limits are set from, many seeds to one
process (set-up dominates a run, so the dozen seeds share one).

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--control 1] [--fault half_batch] [--rows N] [--out file.jsonl]

For each seed: build the dataset, run one fit of the cell's own shape, free
the program's state, replay the fit by the reference. ``--control 1`` also
reads the lower-precision control's gap at the same nodes; ``--fault`` plants
one of ``tests/faults.py`` under the fit first. Prints one JSON line a seed.
Not part of a benchmark run.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "tests")):
    sys.path.insert(0, path)


def data_mismatch(d) -> dict:
    """Cells of the resident binned matrix, and labels, that differ from what
    the reference regenerates and bins itself (first, middle, last chunk)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import datagen, reference
    _, _, cats = datagen.feature_layout(d.data)
    bounds = jnp.asarray(reference.quantile_bounds(
        d.sample, d.params["max_bin"], cats))
    regen = jax.jit(lambda k, c: (lambda X, y: (reference._bins_of(
        X.T, bounds), y))(*datagen.gen_chunk(k, c, d.chunk_rows, d.data)))
    bins_differ = labels_differ = 0
    for c in sorted({0, d.chunks // 2, d.chunks - 1}):
        bins, y = regen(d.key, jnp.int32(c))
        lo = c * d.chunk_rows
        bins_differ += int(jnp.sum(bins != d.dataset.Xbt_d[
            :, lo:lo + d.chunk_rows].astype(jnp.int32)))
        labels_differ += int(jnp.sum(y != d.dataset.y_d[
            lo:lo + d.chunk_rows]))
    return {"bins_differ": bins_differ, "labels_differ": labels_differ}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--check-data", type=int, default=0,
                    help="1: also count where the reference's regenerated "
                         "bins and labels differ from the resident dataset")
    args = ap.parse_args()

    import run as harness
    from lib import gbdt_train
    try:
        spec = harness.load_cell(args.workload)
    except SystemExit:
        # a cell that is out of BENCHMARK.json (PERF.md, Open questions) can
        # still be read from its own files
        with open(os.path.join(BENCH, "workloads",
                               args.workload + ".json")) as f:
            workload = json.load(f)
        with open(os.path.join(BENCH, "configs",
                               workload["config"] + ".json")) as f:
            spec = {"workload": workload, "config": json.load(f),
                    "cell": {"name": args.workload, "chips": 1}}
    device = harness.chip_gate(int(spec["cell"]["chips"]))
    if args.fault:
        import faults
        faults.plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = {"config": spec["config"], "workload": spec["workload"],
               "cell": spec["cell"], "seed": seed,
               "platform": device["platform"], "rows": args.rows}
        d = gbdt_train.Driver(ctx)
        facts = d.set_up()
        d.boosters = [d.warmup_booster]
        d.facts = {"compiles_in_window": 0,
                   "engines": gbdt_train._engine_counts()}
        mismatch = data_mismatch(d) if args.check_data else {}
        d.release()
        t1 = time.perf_counter()
        row = d.compare(control=bool(args.control))
        row.update(mismatch)
        row.update(seed=seed, fault=args.fault, workload=args.workload,
                   platform=device["platform"], rows=d.rows,
                   fit_s=facts["warmup_fit_s"],
                   dataset_s=facts["dataset_s"],
                   compare_s=time.perf_counter() - t1,
                   total_s=time.perf_counter() - t0, **d.reference_facts)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
