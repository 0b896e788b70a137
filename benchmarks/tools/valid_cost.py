"""What the held-out rows cost a validated fit, by operation: one fit with
``metric=None`` (the objective's own, ``binary_logloss``) and one with
``metric="auc"``, each run once cold (its spans say how long the program was
traced, lowered and compiled) and once under the profiler (device seconds by
scope, and by operation for every operation whose shapes carry the held-out
row count).

    python3 benchmarks/tools/valid_cost.py --seed <n> [--tag parent]
        [--chunk-rows N --train-chunks N --valid-chunks N]   # a CPU rehearsal

The table is ``criteo-lgbm-255q``'s: the first ``--train-chunks`` chunks
train, the next ``--valid-chunks`` are held out. The validation set goes in
as ``valid_set=(X, y, None)`` host arrays, which every commit since the seed
takes, so the same file reads the parent (PR 35's "measure first") and the
change. Besides the two fits it times alone, on the host: the binner's
``transform`` of the held-out rows, the download of one validation margin and
an exact AUC of it in NumPy. One JSON line; also written to
``chiprun_out/valid_cost_<tag>.json``. Not part of a benchmark run.
"""

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH, HERE):
    sys.path.insert(0, path)


def _host_auc(scores, y):
    """Exact tie-handled AUC in NumPy (what a host-side metric has to do)."""
    import numpy as np
    order = np.argsort(scores, kind="mergesort")
    s, p = scores[order], y[order] > 0.5
    starts = np.flatnonzero(np.concatenate([[True], np.diff(s) != 0]))
    gpos = np.add.reduceat(p.astype(np.float64), starts)
    gneg = np.add.reduceat((~p).astype(np.float64), starts)
    below = np.concatenate([[0.0], np.cumsum(gneg)[:-1]])
    return float(np.sum(gpos * (below + 0.5 * gneg))
                 / max(p.sum() * (~p).sum(), 1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tag", default="run")
    ap.add_argument("--config", default="criteo-lgbm-255q")
    ap.add_argument("--chunk-rows", type=int, default=0)
    ap.add_argument("--train-chunks", type=int, default=133)
    ap.add_argument("--valid-chunks", type=int, default=6)
    ap.add_argument("--trees", type=int, default=2)
    ap.add_argument("--metrics", default="none,auc")
    args = ap.parse_args()
    out, sys.stdout = sys.stdout, sys.stderr

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import layers
    from lib import datagen, spantree, trace
    from mmlspark_tpu.models.gbdt import booster as gb
    from mmlspark_tpu.models.gbdt.growth import GrowConfig
    from mmlspark_tpu.observability import spans
    from mmlspark_tpu.ops.binning import QuantileBinner, bin_cols_device
    from mmlspark_tpu.parallel import mesh as meshlib
    from mmlspark_tpu.parallel import placement
    from mmlspark_tpu.utils import compile_cache

    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        config = json.load(f)
    p, data = config["params"], config["data"]
    chunk = args.chunk_rows or int(data["chunk_rows"])
    rows, nv = chunk * args.train_chunks, chunk * args.valid_chunks
    result = {"tag": args.tag, "seed": args.seed, "rows": rows,
              "valid_rows": nv, "trees": args.trees,
              "device": jax.devices()[0].device_kind,
              "compile_cache": compile_cache.ensure()}

    key = datagen.seed_key(args.seed)
    _, _, cats = datagen.feature_layout(data)
    F = len(cats) + len(data["numeric"]["log_mean"])
    sample = datagen.sample_rows(key, p["bin_sample_count"], chunk, data)
    binner = QuantileBinner(p["max_bin"], p["bin_sample_count"], 0,
                            cats).fit(sample)
    bounds = jnp.asarray(np.asarray(binner.upper_bounds))
    mesh = meshlib.get_default_mesh()
    bin_dtype = jnp.dtype(p["bin_dtype"])

    def build(k, ub):
        def body(c, carry):
            Xbt, y = carry
            X, yc = datagen.gen_chunk(k, c, chunk, data)
            bt = bin_cols_device(X, ub, out_dtype=bin_dtype)
            return (lax.dynamic_update_slice(Xbt, bt, (0, c * chunk)),
                    lax.dynamic_update_slice(y, yc, (c * chunk,)))
        return lax.fori_loop(0, args.train_chunks, body, (
            jnp.zeros((F, rows), bin_dtype), jnp.zeros((rows,), jnp.float32)))

    cols = placement.sharding(placement.pspec(None, "data"), mesh)
    rows_sh = placement.row_sharding(mesh)
    Xbt_d, y_d = jax.jit(build, out_shardings=(cols, rows_sh))(key, bounds)
    ones = jax.jit(lambda: jnp.ones((rows,), jnp.float32),
                   out_shardings=rows_sh)()
    dataset = gb.LightGBMDataset(binner, Xbt_d, y_d, ones, ones, rows, rows,
                                 mesh, p["max_bin"], cats)

    held = jax.jit(lambda k, c: datagen.gen_chunk(k, c, chunk, data))
    parts = [held(key, jnp.int32(args.train_chunks + c))
             for c in range(args.valid_chunks)]
    Xv = np.concatenate([np.asarray(a) for a, _ in parts])
    yv = np.concatenate([np.asarray(b) for _, b in parts])
    del parts
    result["valid_label_mean"] = float(yv.mean())

    t = time.perf_counter()
    binned_v = binner.transform(Xv)
    result["host_binner_transform_s"] = time.perf_counter() - t
    result["host_binned_dtype"] = str(np.asarray(binned_v).dtype)
    del binned_v

    # the download of one margin and a host AUC of it, alone: a margin with
    # as many distinct values as two 31-leaf trees can leave
    margin_d = jax.jit(lambda k: jnp.round(jax.random.normal(k, (nv, 1))
                                           * 300.0) / 300.0)(key)
    jax.block_until_ready(margin_d)
    t = time.perf_counter()
    margin = np.asarray(margin_d)[:, 0]
    result["host_margin_download_s"] = time.perf_counter() - t
    t = time.perf_counter()
    result["host_auc_value"] = _host_auc(margin, yv)
    result["host_auc_s"] = time.perf_counter() - t

    grow = {k: p[k] for k in GrowConfig._fields if k in p}
    cfg = GrowConfig(**grow)._replace(num_bins=p["max_bin"])
    held_shape = re.compile(r"[\[,]" + str(nv) + r"[\],]")

    def fit(metric):
        return gb.train_booster(
            dataset=dataset, objective=p["objective"], cfg=cfg,
            num_iterations=args.trees, seed=0,
            boost_from_average=p["boost_from_average"],
            valid_set=(Xv, yv, None), early_stopping_rounds=50,
            eval_metric_name=metric)

    def last_fit_tree():
        found = spantree.fits(spantree.events())
        return spantree.tree([found[-1][0]], found[-1][1]) if found else None

    for name in args.metrics.split(","):
        metric = None if name == "none" else name
        row = {}
        t = time.perf_counter()
        booster = fit(metric)
        row["first_fit_s"] = time.perf_counter() - t
        row["first_fit_spans"] = last_fit_tree()
        row["history"] = {k: [float(x) for x in v]
                          for k, v in booster.eval_history.items()} \
            if hasattr(booster, "eval_history") else None
        row["best_iteration"] = int(getattr(booster, "best_iteration", -2))
        t = time.perf_counter()
        fit(metric)
        row["warm_fit_s"] = time.perf_counter() - t
        log_dir = tempfile.mkdtemp(prefix="valid_cost_")
        try:
            trace.start(log_dir)
            t = time.perf_counter()
            fit(metric)
            row["traced_fit_s"] = time.perf_counter() - t
            trace.stop()
            doc = layers.load_scoped(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        row["traced_fit_spans"] = last_fit_tree()
        row.update(layers.reduce_scoped(doc))
        held_ops, all_ops = {}, {}
        for plane in doc["planes"]:
            for line in plane["lines"]:
                for (hlo, op), _, dur in trace._leaves(line["events"]):
                    label = hlo[:200] + " @ " + op[-80:]
                    slot = all_ops.setdefault(label, [0.0, 0])
                    slot[0] += dur * 1e-9
                    slot[1] += 1
                    if held_shape.search(hlo):
                        held_ops[label] = slot
        row["held_out_shape_s"] = sum(v[0] for v in held_ops.values())
        row["held_out_shape_ops"] = sorted(
            ([k, v[0], v[1]] for k, v in held_ops.items()),
            key=lambda r: -r[1])[:14]
        row["top_ops"] = sorted(([k, v[0], v[1]] for k, v in all_ops.items()),
                                key=lambda r: -r[1])[:12]
        result["metric_" + name] = row
        spans.clear_trace()
        print(json.dumps({name: {k: row[k] for k in (
            "first_fit_s", "warm_fit_s", "traced_fit_s", "busy_s",
            "held_out_shape_s")}}), flush=True)

    line = json.dumps(result)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"valid_cost_{args.tag}.json"), "w") as f:
        f.write(line + "\n")
    print(line, file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
