"""``tools/readings.py`` for cells whose ``kind`` is not ``gbdt_train``: the
same readings (many seeds to one process; the control; a planted fault), with
the driver found by the cell's ``kind`` and the faults of ``tests/faults_dp.py``
beside those of ``tests/faults.py``.

    python3 benchmarks/tools/readings_dp.py --workload criteo255q.train4 \\
        --seeds 1,2,3 [--control 1] [--fault shard_dropped] [--rows N] \\
        [--out file.jsonl]

On a CPU give it the cell's shard count as host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``). Not part of a
benchmark run.
"""

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "tests")):
    sys.path.insert(0, path)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import run as harness
    from lib import gbdt_train
    spec = harness.load_cell(args.workload)
    device = harness.chip_gate(int(spec["cell"]["chips"]))
    driver = importlib.import_module("lib." + spec["workload"]["kind"]).Driver
    if args.fault:
        import faults
        import faults_dp
        (faults_dp if args.fault in faults_dp.FAULTS
         else faults).plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        d = driver({"config": spec["config"], "workload": spec["workload"],
                    "cell": spec["cell"], "seed": seed,
                    "platform": device["platform"], "rows": args.rows})
        facts = d.set_up()
        d.boosters = [d.warmup_booster]
        d.facts = {"compiles_in_window": 0,
                   "engines": gbdt_train._engine_counts()}
        d.release()
        t1 = time.perf_counter()
        row = d.compare(control=bool(args.control))
        row.update(seed=seed, fault=args.fault, workload=args.workload,
                   platform=device["platform"], rows=d.rows,
                   fit_s=facts["warmup_fit_s"], dataset_s=facts["dataset_s"],
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
