"""Window driver ``gbdt_train_dp``: ``gbdt_train`` on a table that is
row-sharded over the mesh's ``data`` axis, one shard a chip.

The window, the release and the fits are ``gbdt_train``'s: the program is
entered where the one-chip cells enter it, ``train_booster(dataset=...)`` on
the default mesh. What the shard count changes is here:

* the dataset is built shard by shard, every device generating and binning
  its own chunks of the global chunk order, so the table is the one the
  reference regenerates and no device ever holds more than its shard;
* ``facts["rows"]`` is the rows a device holds (the trace readers match the
  kernel by its first operand ``[F, rows]``, which inside ``shard_map`` is the
  shard's), with the table's beside it, and what building the fit's program
  added to the program's ``gbdt_allreduce_bytes_total``, by ``per``;
* the comparison tells the replay the quantization levels the configuration
  states for this path (``assumed``, "int8 levels"): an int32 sum over a
  *shard's* rows is what has to hold, so the levels follow from the shard's
  rows, not the table's. They are worked out here and not asked of the
  program, so a program that quantizes more coarsely reads as noisier. And it
  spreads the replay's independent
  chunks over the devices, a chunk a device at a time. The reference's own
  code runs every chunk (``reference._chunk_pass``) and reads the sums
  (``reference._read``); it still knows nothing of shards.
"""

from __future__ import annotations

import time

import numpy as np

from . import datagen, gbdt_train, reference
from .gbdt_train import CONTROL_QMAX, _counter_total

_ALLREDUCE_PER = ("tree", "round", "level")


def _allreduce_bytes() -> dict:
    return {per: _counter_total("gbdt_allreduce_bytes_total", per=per)
            for per in _ALLREDUCE_PER}


class Driver(gbdt_train.Driver):
    def __init__(self, ctx: dict):
        super().__init__(ctx)
        from mmlspark_tpu.parallel import mesh as meshlib
        self.mesh = meshlib.get_default_mesh()
        self.shards = meshlib.num_shards(self.mesh)
        if ctx["platform"] == "tpu" and \
                dict(self.mesh.shape) != self.config["mesh"]:
            raise SystemExit(
                f"the configuration's mesh is {self.config['mesh']}, the "
                f"default mesh here is {dict(self.mesh.shape)}")
        # a CPU rehearsal shards over the host devices there are
        if self.chunks % self.shards:
            # a rehearsal's toy table: one chunk a shard, for the reference
            # too (it plans its chunks from the same block)
            self.data = dict(self.data, chunk_rows=self.rows // self.shards)
            self.chunk_rows, self.chunks = datagen.chunk_plan(self.rows,
                                                              self.data)
        self.rows_local = self.rows // self.shards

    # -- set-up ------------------------------------------------------------

    def set_up(self) -> dict:
        """As ``gbdt_train``'s, with the dataset made by one ``shard_map``
        program: shard ``s`` holds chunks ``[s * chunks / shards, ...)``."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from mmlspark_tpu.models.gbdt import booster as gb
        from mmlspark_tpu.models.gbdt.growth import GrowConfig
        from mmlspark_tpu.ops.binning import QuantileBinner, bin_cols_device
        from mmlspark_tpu.parallel.compat import shard_map
        from mmlspark_tpu.parallel.placement import pspec
        from mmlspark_tpu.utils import compile_cache

        facts, t = {}, time.perf_counter()
        facts["compile_cache"] = compile_cache.ensure()
        mesh = self.mesh
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
        # the levels a side at which a shard's int32 sums cannot overflow, as
        # the configuration's file states them under "int8 levels"
        self.quant_levels = (
            max(1, min(127, (2 ** 31 - 1) // self.rows_local))
            if p.get("quantized_grad") else 0)
        facts["binner_s"] = time.perf_counter() - t

        t = time.perf_counter()
        bin_dtype = jnp.dtype(p["bin_dtype"])
        self.bin_bytes = bin_dtype.itemsize
        rows, rows_local = self.rows, self.rows_local
        chunk_rows, chunks_local = self.chunk_rows, self.chunks // self.shards

        def build_shard(key, upper_bounds):
            first = lax.axis_index("data") * chunks_local

            def body(c, carry):
                Xbt, y = carry
                X, yc = datagen.gen_chunk(key, first + c, chunk_rows, data)
                bt = bin_cols_device(X, upper_bounds, out_dtype=bin_dtype)
                return (lax.dynamic_update_slice(Xbt, bt,
                                                 (0, c * chunk_rows)),
                        lax.dynamic_update_slice(y, yc, (c * chunk_rows,)))
            return lax.fori_loop(0, chunks_local, body, (
                jnp.zeros((F, rows_local), bin_dtype),
                jnp.zeros((rows_local,), jnp.float32)))

        Xbt_d, y_d = jax.jit(shard_map(
            build_shard, mesh=mesh, in_specs=(pspec(), pspec()),
            out_specs=(pspec(None, "data"), pspec("data")),
            check_vma=False))(self.key, jnp.asarray(self.program_bounds))
        vmask_d = jax.jit(shard_map(
            lambda: jnp.ones((rows_local,), jnp.float32), mesh=mesh,
            in_specs=(), out_specs=pspec("data"), check_vma=False))()
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
        t, staged = time.perf_counter(), _allreduce_bytes()
        self.warmup_booster = self._fit()
        facts["warmup_fit_s"] = time.perf_counter() - t
        # the first fit builds the program, and the counter counts builds
        self.allreduce_staged = {per: v - staged[per] for per, v
                                 in _allreduce_bytes().items()}
        return facts

    # -- the timed window ---------------------------------------------------

    def window(self, seconds: float) -> dict:
        facts = super().window(seconds)
        facts.update(rows=self.rows_local, table_rows=self.rows,
                     shards=self.shards,
                     allreduce_bytes=self.allreduce_staged)
        return facts

    # -- the comparison -----------------------------------------------------

    def _replay(self, trees: dict, base_score: float, bounds: np.ndarray,
                control_qmax: int) -> dict:
        """``reference.replay`` with its chunks handed to the devices, one a
        device at a time: the same pass over every chunk, the same float64
        sums on the host, the same readings."""
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.parallel.compat import shard_map
        from mmlspark_tpu.parallel.placement import pspec

        p, data, chunk_rows = self.params, self.data, self.chunk_rows
        _, _, cat_cols = datagen.feature_layout(data)
        L, B = int(p["num_leaves"]), int(p["max_bin"])
        T = trees["feat"].shape[0]
        F = bounds.shape[0]
        S = 5 if control_qmax else 3
        leaf_nodes, under = reference.leaf_layout(trees, L)
        dev_trees = {k: jnp.asarray(trees[k]) for k in (
            "feat", "thr_raw", "left", "right", "is_leaf", "leaf_value",
            "cat_bitset")}

        def one_chunk(c, key, tr, bd, base, ln):
            hist, label_sum, amax = reference._chunk_pass(
                key, c[0], tr, bd, base, ln, chunk_rows=chunk_rows,
                data=data, num_bins=B, cat_cols=cat_cols,
                control_qmax=control_qmax)
            return hist[None], label_sum[None], amax[None]

        spread = pspec("data")
        step = jax.jit(shard_map(
            one_chunk, mesh=self.mesh,
            in_specs=(spread,) + (pspec(),) * 5,
            out_specs=(spread, spread, spread), check_vma=False))
        fixed = (self.key, dev_trees, jnp.asarray(bounds),
                 jnp.float32(base_score), jnp.asarray(leaf_nodes))
        hist = np.zeros((T, F, S * L, B), np.float64)
        label_sum, pending, amax = 0.0, None, np.zeros((T, 2))
        steps = self.chunks // self.shards
        for i in range(steps + 1):                 # one step in flight
            nxt = (step(jnp.arange(i * self.shards, (i + 1) * self.shards,
                                   dtype=jnp.int32), *fixed)
                   if i < steps else None)
            if pending is not None:
                hist += np.asarray(pending[0], np.float64).sum(axis=0)
                label_sum += float(np.asarray(pending[1], np.float64).sum())
                amax = np.maximum(amax, np.asarray(pending[2],
                                                   np.float64).max(axis=0))
            pending = nxt
        hist = hist.reshape(T, F, S, L, B).transpose(0, 3, 1, 2, 4)
        out = reference._read(hist, amax, under, trees, p, cat_cols,
                              bool(control_qmax), self.quant_levels)
        out["label_mean"] = label_sum / self.rows
        return out

    def compare(self, control: bool = False) -> dict:
        """``gbdt_train``'s comparison, read by :meth:`_replay`."""
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
        readings = self._replay(ref, float(picked.base_score[0]), bounds,
                                qmax)
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
