"""Window driver ``gbdt_trainval``: ``gbdt_train`` for a configuration that
holds rows out and scores them after every tree. Whole fits of

    train_booster(dataset=<training rows>, valid_set=<held-out dataset>,
                  eval_metric_name=<metric>, early_stopping_rounds=<n>)

back to back, both datasets built once, on the device, from the seed: the
training set from the table's first chunks, as ``gbdt_train`` builds it, the
held-out set from the chunks the configuration's ``validation`` block names,
binned by the training set's binner (``LightGBMDataset`` with the same
binner and mesh: what ``construct(reference=train)`` gives for host arrays).

The window, the release and ``gbdt_train``'s comparison (over the training
rows) are ``gbdt_train``'s. Added:

* ``facts["valid_rows"]`` and ``facts["valid_metric_evals"]``: the window's
  ``gbdt_valid_metric_total{where=device|host}`` (``layer_metrics/
  valid_eval_share_pct.py`` and ``valid_metric_host_evals.py`` read them).
* ``fits_differ`` also counts a fit whose recorded metric history or best
  iteration differs from the picked fit's.
* ``auc_gap``: the worst iteration's recorded metric against
  ``reference_auc.replay``'s exact AUC of the regenerated held-out rows under
  the picked fit's trees; ``best_iter_differs``: the fit's ``best_iteration``
  against the first iteration of the best metric, the recorded one's and the
  reference's (0, 1 or 2 disagreements). Under ``control`` the reference's
  bfloat16 margin stands in the program's place.

The final validation margin stays inside the fused program's ``while_loop``
(its outputs are the trees, the metric history and two counters), so there
is no ``valid_margin_gap``: handing the margin out would take a new output
of ``train_booster``. ``auc_gap`` reads it through the metric: a row whose
margin is off moves its rank among 2.9 M.
"""

from __future__ import annotations

import time

import numpy as np

# The program's device metric. A program without it takes metric="auc" to a
# host loop that downloads the margin every round, which is not the fit this
# cell times, so the cell ends here, at once, on such a program.
from mmlspark_tpu.models.gbdt.objectives import auc_device  # noqa: F401

from . import datagen, gbdt_train, reference, reference_auc
from .gbdt_train import _counter_total


def _valid_evals() -> dict:
    return {where: _counter_total("gbdt_valid_metric_total", where=where)
            for where in ("device", "host")}


class Driver(gbdt_train.Driver):

    def __init__(self, ctx: dict):
        super().__init__(ctx)
        v = self.config["validation"]
        self.metric = v["metric"]
        if ctx.get("rows"):
            # a CPU rehearsal: one training chunk, an eighth as many rows
            # held out, as chunk 1 of their own chunking
            self.valid_plan = {"first_chunk": 1, "chunks": 1,
                               "chunk_rows": max(1024, self.rows // 8)}
        else:
            self.valid_plan = {"first_chunk": int(v["first_chunk"]),
                               "chunks": int(v["chunks"]),
                               "chunk_rows": self.chunk_rows}
        self.valid_rows = (self.valid_plan["chunks"]
                           * self.valid_plan["chunk_rows"])
        if not ctx.get("rows") and (
                self.valid_rows != self.config["valid_rows"]
                or v["first_chunk"] != self.chunks):
            raise ValueError("the configuration's validation block does not "
                             "follow its training rows")

    # -- set-up ------------------------------------------------------------

    def set_up(self) -> dict:
        """As ``gbdt_train``'s, with the held-out dataset beside the
        training one and the validated fit as the warm-up fit."""
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
        cols = placement.sharding(placement.pspec(None, "data"), mesh)
        rows_sh = placement.row_sharding(mesh)

        def table(first, chunks, chunk_rows):
            """Chunks ``first .. first + chunks`` binned ``[F, rows]``, their
            labels and unit weights, resident."""
            rows = chunks * chunk_rows

            def build(key, upper_bounds):
                def body(i, carry):
                    Xbt, y = carry
                    X, yc = datagen.gen_chunk(key, first + i, chunk_rows,
                                              data)
                    bt = bin_cols_device(X, upper_bounds,
                                         out_dtype=bin_dtype)
                    return (lax.dynamic_update_slice(Xbt, bt,
                                                     (0, i * chunk_rows)),
                            lax.dynamic_update_slice(y, yc,
                                                     (i * chunk_rows,)))
                return lax.fori_loop(0, chunks, body, (
                    jnp.zeros((F, rows), bin_dtype),
                    jnp.zeros((rows,), jnp.float32)))

            Xbt_d, y_d = jax.jit(build, out_shardings=(cols, rows_sh))(
                self.key, jnp.asarray(self.program_bounds))
            ones = jax.jit(lambda: jnp.ones((rows,), jnp.float32),
                           out_shardings=rows_sh)()
            jax.block_until_ready((Xbt_d, y_d, ones))
            return gb.LightGBMDataset(binner, Xbt_d, y_d, ones, ones, rows,
                                      rows, mesh, p["max_bin"], cats)

        self.dataset = table(0, self.chunks, self.chunk_rows)
        self.valid_dataset = table(self.valid_plan["first_chunk"],
                                   self.valid_plan["chunks"],
                                   self.valid_plan["chunk_rows"])
        facts["dataset_s"] = time.perf_counter() - t

        grow = {k: p[k] for k in GrowConfig._fields if k in p}
        cfg = GrowConfig(**grow)._replace(num_bins=p["max_bin"])
        v = self.config["validation"]
        self._fit = lambda: gb.train_booster(
            dataset=self.dataset, valid_set=self.valid_dataset,
            eval_metric_name=self.metric,
            early_stopping_rounds=int(v["early_stopping_rounds"]),
            metric_eval_period=int(v["metric_eval_period"]),
            objective=p["objective"], cfg=cfg,
            num_iterations=self.trees_per_fit,
            seed=int(self.workload["fit_seed"]),
            boost_from_average=p["boost_from_average"])
        t = time.perf_counter()
        self.warmup_booster = self._fit()
        facts["warmup_fit_s"] = time.perf_counter() - t
        return facts

    # -- the timed window ---------------------------------------------------

    def window(self, seconds: float) -> dict:
        before = _valid_evals()
        facts = super().window(seconds)
        facts["valid_rows"] = self.valid_rows
        facts["valid_metric_evals"] = {
            where: count - before[where]
            for where, count in _valid_evals().items()}
        return facts

    def release(self) -> None:
        ds = self.valid_dataset
        for arr in {id(a): a for a in (ds.Xbt_d, ds.y_d, ds.vmask_d,
                                       ds.w_d)}.values():
            arr.delete()
        self.valid_dataset = None
        super().release()

    # -- the comparison -----------------------------------------------------

    def _tree_arrays(self, booster) -> dict:
        """``gbdt_train``'s arrays and, a value a tree so that they ride
        along wherever trees are compared or indexed, the fit's recorded
        metric and its best iteration."""
        trees = super()._tree_arrays(booster)
        T = trees["feat"].shape[0]
        history = np.full(T, np.nan, np.float64)
        recorded = booster.eval_history.get(self.metric, [])[:T]
        history[:len(recorded)] = recorded
        trees["valid_metric"] = history
        trees["best_iteration"] = np.full(T, booster.best_iteration)
        return trees

    def compare(self, control: bool = False) -> dict:
        out = super().compare(control)
        picked = self.boosters[self.ctx["seed"] % len(self.boosters)]
        trees = self._tree_arrays(picked)
        _, _, cats = datagen.feature_layout(self.data)
        bounds = reference.quantile_bounds(self.sample,
                                           self.params["max_bin"], cats)
        ref = reference_auc.replay(
            self.key, self.valid_plan, self.data, trees,
            float(picked.base_score[0]), bounds, control=control)
        exact = np.asarray(ref["auc"], np.float64)
        recorded = trees["valid_metric"]
        # the fit may have stopped early or been truncated to its best
        # iteration: every tree it kept has a recorded metric
        gaps = np.abs(recorded - exact)
        out["auc_gap"] = float(np.max(gaps)) if np.all(
            np.isfinite(gaps)) else float("inf")
        best = int(picked.best_iteration)
        out["best_iter_differs"] = int(best != int(np.argmax(exact))) + int(
            best != int(np.argmax(np.float32(recorded))))
        if control:
            out["program_auc_gap"] = out["auc_gap"]
            out["auc_gap"] = float(np.max(np.abs(
                np.asarray(ref["control_auc"]) - exact)))
        self.reference_facts.update(
            auc_exact=ref["auc"], auc_recorded=[float(x) for x in recorded],
            auc_control=ref.get("control_auc"), best_iteration=best,
            valid_label_mean=ref["label_mean"])
        return out
