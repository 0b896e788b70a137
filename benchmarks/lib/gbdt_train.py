"""Window driver ``gbdt_train``: whole fits of the program's
``train_booster(dataset=...)`` back to back on a dataset that is built once,
on the device, from the seed.

From the program it takes the system under test and its counters:
``ops.binning`` (binner and device binning), ``booster.LightGBMDataset``,
``booster.train_booster``, ``utils.compile_cache.ensure`` and the metrics
registry. The data, the timing, the needed work and the comparison are the
benchmark's own.
"""

from __future__ import annotations

import time

import numpy as np

from . import datagen, reference

# the nearest precision below the configuration's, as levels a side of the
# control's symmetric quantizer: int8 under bf16, int4 under int8
CONTROL_QMAX = {"bf16": 127, "int8": 7}

_COMPILE_COUNTERS = ("gbdt_program_builds_total",
                     "persistent_compile_cache_misses_total")
_TREE_FIELDS = ("feat", "thr_bin", "left", "right", "is_leaf", "leaf_value",
                "node_cnt", "node_grad", "node_hess", "node_count",
                "cat_bitset")


def _counter_total(family: str, **labels) -> int:
    from mmlspark_tpu.observability import metrics
    series = (metrics.get_registry().snapshot().get(family) or {}).get(
        "series", [])
    return int(sum(s["value"] for s in series
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items())))


def _engine_counts() -> dict:
    return {e: _counter_total("hist_engine_selected_total", engine=e)
            for e in ("pallas", "onehot", "scatter")}


class Driver:
    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.config, self.workload = ctx["config"], ctx["workload"]
        self.params, self.data = self.config["params"], self.config["data"]
        self.rows = int(ctx.get("rows") or self.config["rows"])
        self.chunk_rows, self.chunks = datagen.chunk_plan(self.rows,
                                                          self.data)
        self.trees_per_fit = int(self.workload["trees_per_fit"])
        self.boosters = []

    # -- set-up ------------------------------------------------------------

    def set_up(self) -> dict:
        """Compile cache, dataset on the device, one warm-up fit of the
        window's own shape. Returns set-up facts (seconds of each part)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from mmlspark_tpu.models.gbdt import booster as gb
        from mmlspark_tpu.models.gbdt.growth import GrowConfig
        from mmlspark_tpu.ops.binning import QuantileBinner, bin_cols_device
        from mmlspark_tpu.parallel import mesh as meshlib
        from mmlspark_tpu.parallel import placement
        from mmlspark_tpu.utils import compile_cache

        facts, t = {}, time.perf_counter()
        facts["compile_cache"] = compile_cache.ensure()
        self.key = datagen.seed_key(self.ctx["seed"])
        p, data = self.params, self.data
        _, _, cats = datagen.feature_layout(data)
        F = len(cats) + len(data["numeric"]["log_mean"])
        self.num_features = F
        self.sample = datagen.sample_rows(self.key, p["bin_sample_count"],
                                          self.chunk_rows, data)
        binner = QuantileBinner(p["max_bin"], p["bin_sample_count"], 0,
                                cats).fit(self.sample)
        self.program_bounds = np.asarray(binner.upper_bounds)
        facts["binner_s"] = time.perf_counter() - t

        t = time.perf_counter()
        mesh = meshlib.get_default_mesh()
        bin_dtype = jnp.dtype(p["bin_dtype"])
        self.bin_bytes = bin_dtype.itemsize
        rows, chunk_rows, chunks = self.rows, self.chunk_rows, self.chunks

        def build(key, upper_bounds):
            def body(c, carry):
                Xbt, y = carry
                X, yc = datagen.gen_chunk(key, c, chunk_rows, data)
                bt = bin_cols_device(X, upper_bounds, out_dtype=bin_dtype)
                return (lax.dynamic_update_slice(Xbt, bt,
                                                 (0, c * chunk_rows)),
                        lax.dynamic_update_slice(y, yc, (c * chunk_rows,)))
            return lax.fori_loop(0, chunks, body, (
                jnp.zeros((F, rows), bin_dtype),
                jnp.zeros((rows,), jnp.float32)))

        cols = placement.sharding(placement.pspec(None, "data"), mesh)
        rows_sh = placement.row_sharding(mesh)
        Xbt_d, y_d = jax.jit(build, out_shardings=(cols, rows_sh))(
            self.key, jnp.asarray(self.program_bounds))
        vmask_d = jax.jit(lambda: jnp.ones((rows,), jnp.float32),
                          out_shardings=rows_sh)()
        jax.block_until_ready((Xbt_d, y_d, vmask_d))
        self.dataset = gb.LightGBMDataset(binner, Xbt_d, y_d, vmask_d,
                                          vmask_d, rows, rows, mesh,
                                          p["max_bin"], cats)
        facts["dataset_s"] = time.perf_counter() - t

        grow = {k: p[k] for k in GrowConfig._fields if k in p}
        cfg = GrowConfig(**grow)._replace(num_bins=p["max_bin"])
        self._fit = lambda: gb.train_booster(
            dataset=self.dataset, objective=p["objective"], cfg=cfg,
            num_iterations=self.trees_per_fit,
            seed=int(self.workload["fit_seed"]),
            boost_from_average=p["boost_from_average"])
        t = time.perf_counter()
        self.warmup_booster = self._fit()
        facts["warmup_fit_s"] = time.perf_counter() - t
        return facts

    # -- the timed window ---------------------------------------------------

    def window(self, seconds: float) -> dict:
        """Fits until ``seconds`` have passed; all trees over all the time."""
        before = {c: _counter_total(c) for c in _COMPILE_COUNTERS}
        t0 = time.perf_counter()
        import jax
        while True:
            with jax.profiler.TraceAnnotation("bench_fit"):
                self.boosters.append(self._fit())   # trees land on the host
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        fits = len(self.boosters)
        trees = fits * self.trees_per_fit
        node_cnt_sum = 0.0
        for b in self.boosters:
            for t in range(b.num_trees):
                used = int(b.trees.node_count[t])
                node_cnt_sum += float(np.sum(
                    b.trees.node_cnt[t, :used], dtype=np.float64))
        engines = _engine_counts()
        self.facts = {
            "attempted": fits, "failed": 0, "trees": trees,
            "rows": self.rows,
            "window_s": t1 - t0, "node_cnt_sum": node_cnt_sum,
            "num_features": self.num_features, "bin_bytes": self.bin_bytes,
            "stats_dtype": self.config["stats_dtype"],
            "compiles_in_window": sum(
                _counter_total(c) - before[c] for c in _COMPILE_COUNTERS),
            "engines": engines,
            "end_to_end": {"train_trees_per_s": trees / (t1 - t0)},
        }
        return self.facts

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        ds = self.dataset
        for arr in {id(a): a for a in (ds.Xbt_d, ds.y_d, ds.vmask_d,
                                       ds.w_d)}.values():
            arr.delete()
        self.dataset = self._fit = None

    # -- the comparison -----------------------------------------------------

    def _tree_arrays(self, booster) -> dict:
        trees = {k: np.asarray(getattr(booster.trees, k))
                 for k in _TREE_FIELDS}
        trees["thr_raw"] = np.asarray(booster.thr_raw, np.float32)
        return trees

    def compare(self, control: bool = False) -> dict:
        """``{name: value}`` of every number compared. The window's fits and
        the warm-up fit see the same data and fit seed, so they must agree to
        the bit; one of the window's, drawn from the seed, is replayed by the
        reference."""
        picked = self.boosters[self.ctx["seed"] % len(self.boosters)]
        ref = self._tree_arrays(picked)
        differ = sum(
            any(not np.array_equal(a[k], ref[k]) for k in ref)
            for a in map(self._tree_arrays,
                         self.boosters + [self.warmup_booster]))
        _, _, cats = datagen.feature_layout(self.data)
        bounds = reference.quantile_bounds(self.sample,
                                           self.params["max_bin"], cats)
        out = {
            "fits_differ": differ,
            "bounds_differ": int(np.sum(bounds != self.program_bounds)),
            "compiles_in_window": self.facts["compiles_in_window"],
        }
        if self.ctx["platform"] == "tpu":
            out["other_engines"] = sum(
                v for e, v in self.facts["engines"].items() if e != "pallas")
        qmax = CONTROL_QMAX[self.config["stats_dtype"]] if control else 0
        levels = 0
        if self.params.get("quantized_grad"):
            # the program's own bound on its int32 accumulator, as the
            # configuration's file states it under "int8 levels"
            levels = max(1, min(127, (2 ** 31 - 1) // self.rows))
        readings = reference.replay(
            self.key, self.rows, self.data, self.params, ref,
            float(picked.base_score[0]), bounds, control_qmax=qmax,
            quant_levels=levels)
        names = [k for k in ("count_gap", "leaf_gap", "leaf_noise",
                             "gain_gap", "gain_loss") if k in readings]
        out.update({k: readings[k] for k in names})
        if control:
            for k in names:
                if "control_" + k in readings:
                    out["program_" + k] = out[k]
                    out[k] = readings["control_" + k]
        self.reference_facts = {k: v for k, v in readings.items()
                                if k in ("nodes", "label_mean")
                                or k.endswith("_at")}
        return out
