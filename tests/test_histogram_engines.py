"""Cross-engine histogram equivalence + backend-adaptive resolution.

The three histogram engines — ``pallas`` (TPU kernel, run here through the
interpreter), ``onehot`` (XLA MXU-shaped matmul fallback) and ``scatter``
(segment-sum scatter-adds, the CPU/GPU formulation) — must produce equal
histograms through the SAME ``histogram``/``histogram_cols``/
``node_histogram`` entry points: count channel exact, grad/hess to f32
accumulation tolerance, int8 quantized stats exactly. Training on top of
them must therefore grow bit-identical tree STRUCTURE. These tests pin
all of that, plus the resolver rules (the one place the engine is chosen),
the ``"auto"`` sentinel's absence from program cache keys, and the donated
host-loop step buffers.
"""

import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.ops import histogram as H
from mmlspark_tpu.ops.histogram import (histogram, histogram_cols,
                                        node_histogram, quantize_stats,
                                        resolve_engine)

ENGINES = ["onehot", "scatter", "pallas"]


def _force_engine(monkeypatch, engine: str) -> None:
    """Pin the resolver to one engine (pallas rides the interpreter on
    CPU so the real kernel logic runs without TPU hardware)."""
    monkeypatch.delenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", raising=False)
    if engine == "pallas":
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "pallas")
    else:
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET", raising=False)
        monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", engine)


class TestResolver:
    # resolve_engine() is a function of MMLSPARK_TPU_HIST_ENGINE, the
    # interpreter switch and the backend (the CPU here), nothing else
    @pytest.mark.parametrize("env,interpret,want", [
        (None, False, "scatter"), (None, True, "pallas"),
        ("", False, "scatter"),
        ("auto", False, "scatter"), ("auto", True, "pallas"),
        ("scatter", False, "scatter"), ("scatter", True, "scatter"),
        ("onehot", False, "onehot"), ("onehot", True, "onehot"),
        ("pallas", True, "pallas"), (" Pallas ", True, "pallas"),
    ])
    def test_resolve_engine_table(self, env, interpret, want, monkeypatch):
        monkeypatch.delenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", raising=False)
        if env is None:
            monkeypatch.delenv("MMLSPARK_TPU_HIST_ENGINE", raising=False)
        else:
            monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", env)
        if interpret:
            monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET", raising=False)
        assert resolve_engine() == want

    def test_explicit_pallas_that_cannot_lower_raises(self, monkeypatch):
        # a pinned engine never silently becomes another one: no TPU
        # backend and no interpreter here, so the kernel cannot lower
        monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "pallas")
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET", raising=False)
        monkeypatch.delenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", raising=False)
        with pytest.raises(RuntimeError, match="cannot lower"):
            resolve_engine()
        # ... and the kill switch contradicts the pin instead of
        # outranking it
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1")
        with pytest.raises(RuntimeError, match="DISABLE_PALLAS_HIST"):
            resolve_engine()

    def test_disable_pallas_under_auto_picks_another_engine(self,
                                                            monkeypatch):
        monkeypatch.delenv("MMLSPARK_TPU_HIST_ENGINE", raising=False)
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1")
        assert resolve_engine() == "scatter"

    def test_interpret_mode_on_a_tpu_backend_is_an_error(self, monkeypatch):
        # on the chip the interpreter would stand in for the Mosaic kernel
        # unnoticed — the variable is refused there, at resolution and at
        # the pallas_call
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("MMLSPARK_TPU_HIST_ENGINE", raising=False)
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET", raising=False)
        assert resolve_engine() == "pallas"
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        with pytest.raises(RuntimeError, match="PALLAS_INTERPRET"):
            resolve_engine()
        with pytest.raises(RuntimeError, match="PALLAS_INTERPRET"):
            H._interpret_mode()

    def test_tpu_shape_gate_fall_is_loud(self, monkeypatch):
        # a shape the kernel cannot tile falls to onehot — on a TPU with a
        # flight event and a warning naming the shape, never silently
        import jax

        from mmlspark_tpu.observability import flight
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("MMLSPARK_TPU_HIST_ENGINE", raising=False)
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(H, "_vmem_budget", lambda: 1 << 20)
        flight.clear()
        assert H._select_engine(4096, 28, 93, 255, fused_w=31) == "onehot"
        ev = [e for e in flight.events() if e["kind"] == "hist_engine"]
        assert len(ev) == 1 and ev[0]["event"] == "pallas_shape_gate"
        assert (ev[0]["n"], ev[0]["F"], ev[0]["S"], ev[0]["B"],
                ev[0]["W"]) == (4096, 28, 93, 255, 31)
        assert ev[0]["vmem_budget_bytes"] == 1 << 20

    def test_bad_value_raises(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "mxu")
        with pytest.raises(ValueError, match="MMLSPARK_TPU_HIST_ENGINE"):
            resolve_engine()


def _ref_hist(binned, stats, B):
    """f64 numpy reference on bf16-rounded stats (the rounding every
    engine applies to grad/hess inputs)."""
    n, F = binned.shape
    S = stats.shape[1]
    sb = stats.astype(jnp.bfloat16).astype(np.float64)
    out = np.zeros((F, S, B), np.float64)
    for r in range(n):
        out[:, :, 0] += 0  # keep shape
        for f in range(F):
            out[f, :, binned[r, f]] += sb[r]
    return out


class TestCrossEngineEquivalence:
    """All engines agree through the same entry points."""

    @pytest.mark.parametrize("engine", ENGINES)
    @pytest.mark.parametrize("B", [255, 63, 31])
    def test_histogram_cols_matches_reference(self, engine, B, monkeypatch):
        _force_engine(monkeypatch, engine)
        rng = np.random.default_rng(0)
        n, F, S = 1200, 5, 6
        binned = rng.integers(0, B, size=(n, F), dtype=np.int32)
        stats = rng.normal(size=(n, S)).astype(np.float32)
        got = np.asarray(histogram_cols(jnp.asarray(binned.T),
                                        jnp.asarray(stats.T), B))
        want = _ref_hist(binned, stats, B)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        # row-major wrapper rides the same engine
        got_rm = np.asarray(histogram(jnp.asarray(binned),
                                      jnp.asarray(stats), B))
        np.testing.assert_array_equal(got, got_rm)

    @pytest.mark.parametrize("engine", ENGINES)
    # (255, 3), (255, 16): the pallas kernel's folded layout; (255, 22): the
    # first width past it (ops/histogram.py::_fold_words)
    @pytest.mark.parametrize("B,W", [(255, 3), (63, 16), (31, 2), (255, 16),
                                     (255, 22)])
    def test_node_histogram_cross_engine(self, engine, B, W, monkeypatch):
        # count channel must be exact; grad/hess to f32 tolerance
        rng = np.random.default_rng(1)
        n, F = 1100, 6
        binned_t = jnp.asarray(rng.integers(0, B, size=(F, n),
                                            dtype=np.int32))
        pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
        grad = rng.normal(size=n).astype(np.float32)
        mask = (rng.uniform(size=n) < 0.9).astype(np.float32)
        base = jnp.asarray(np.stack([grad * mask,
                                     np.abs(grad) * mask, mask]))
        _force_engine(monkeypatch, "onehot")
        want = np.asarray(node_histogram(binned_t, pos, base, W, B))
        _force_engine(monkeypatch, engine)
        got = np.asarray(node_histogram(binned_t, pos, base, W, B))
        assert got.shape == (F, 3 * W, B)
        # channel layout: out[f, w*3 + 2] is the count channel — exact
        np.testing.assert_array_equal(got[:, 2::3, :], want[:, 2::3, :])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("engine", ENGINES)
    @pytest.mark.parametrize("B,W", [(63, 4), (255, 16), (255, 21),
                                     (129, 16), (255, 22)])
    def test_node_histogram_quantized_exact(self, engine, B, W, monkeypatch):
        # int8 stats accumulate in int32 on every engine — exact equality
        # after dequantization, in the pallas kernel's plain layout and in
        # its folded one (255 and 129 bins up to W = 21)
        rng = np.random.default_rng(2)
        n, F = 1100, 5
        binned_t = jnp.asarray(rng.integers(0, B, size=(F, n),
                                            dtype=np.int32))
        pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
        base = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
        q, scales = quantize_stats(base)
        _force_engine(monkeypatch, "onehot")
        want = np.asarray(node_histogram(binned_t, pos, q, W, B,
                                         scales=scales))
        _force_engine(monkeypatch, engine)
        got = np.asarray(node_histogram(binned_t, pos, q, W, B,
                                        scales=scales))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("engine", ENGINES)
    @pytest.mark.parametrize("dtype", ["uint8", "int16"])
    def test_narrow_bin_storage_identical(self, engine, dtype, monkeypatch):
        # bin-id storage dtype is lossless on every engine
        _force_engine(monkeypatch, engine)
        rng = np.random.default_rng(3)
        n, F, B, W = 900, 4, 255, 3
        b32 = rng.integers(0, B, size=(F, n), dtype=np.int32)
        pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
        base = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
        got = np.asarray(node_histogram(jnp.asarray(b32.astype(dtype)),
                                        pos, base, W, B))
        want = np.asarray(node_histogram(jnp.asarray(b32), pos, base, W, B))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("engine", ENGINES)
    def test_categorical_bin_distribution(self, engine, monkeypatch):
        # categorical features produce heavily skewed low-cardinality ids
        # with a catch-all bin — the distribution shape that trips sparse
        # scatter paths. Compare against onehot on the exact count channel
        # and f32-tolerance stats.
        rng = np.random.default_rng(4)
        n, F, B, W = 1500, 3, 31, 4
        # zipf-ish skew clipped into [0, B): most rows in a few categories
        ids = np.minimum(rng.zipf(1.5, size=(F, n)) - 1, B - 1)
        binned_t = jnp.asarray(ids.astype(np.int32))
        pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
        g = rng.normal(size=n).astype(np.float32)
        base = jnp.asarray(np.stack([g, np.abs(g), np.ones_like(g)]))
        _force_engine(monkeypatch, "onehot")
        want = np.asarray(node_histogram(binned_t, pos, base, W, B))
        _force_engine(monkeypatch, engine)
        got = np.asarray(node_histogram(binned_t, pos, base, W, B))
        np.testing.assert_array_equal(got[:, 2::3, :], want[:, 2::3, :])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestTotalsMatchHistogramRounding:
    """Split search derives each right child as ``total - left`` with
    ``left`` a histogram prefix sum, so node totals must sum the SAME
    bf16-rounded stats the histograms sum. Totals of raw f32 stats hand the
    whole dataset's rounding residue to one small leaf (found by
    chip_smoke.py at 1M rows: leaf hessians near zero, leaf values in the
    thousands, while 50k-row fits looked healthy)."""

    @pytest.mark.parametrize("engine", ENGINES)
    @pytest.mark.parametrize("blocks", [0, 4])
    def test_stat_totals_equal_root_histogram_sums(self, engine, blocks,
                                                   monkeypatch):
        import jax

        from mmlspark_tpu.models.gbdt.growth import _stat_totals
        _force_engine(monkeypatch, engine)
        n, F, B = 20_000, 3, 63
        rng = np.random.default_rng(0)
        binned_t = jnp.asarray(rng.integers(0, B, size=(F, n),
                                            dtype=np.int32))
        # hessians clustered at one value that bf16 rounds DOWN: the
        # residue is systematic, ~4e-4 per row (the early-iteration
        # binary-logloss regime)
        base = jnp.asarray(np.stack([
            rng.normal(size=n).astype(np.float32),
            np.full(n, 0.2475, np.float32),
            np.ones(n, np.float32)]))
        tot = np.asarray(jax.jit(lambda b: _stat_totals(
            b, None, None, blocks, n // blocks if blocks else 0))(base))
        hist = np.asarray(node_histogram(
            binned_t, jnp.zeros(n, jnp.int32), base, 1, B))     # [F, 3, B]
        for f in range(F):
            np.testing.assert_allclose(tot, hist[f].sum(axis=-1),
                                       rtol=2e-6, atol=1e-3)
        # the unrounded total is what the parent commit returned: off by
        # the residue that used to reach a leaf
        assert abs(float(np.asarray(base[1], np.float64).sum()) - tot[1]) > 5


    def test_scatter_rounding_is_one_xla_cannot_elide(self, monkeypatch):
        # the scatter engine accumulates in f32: an astype(bf16) ahead of it
        # is an f32 -> bf16 -> f32 round trip, which XLA on TPU elides
        # inside one program — the engine then sums UNROUNDED stats against
        # rounded totals. Only the chip shows the elision (chip_smoke.py's
        # kernel phase runs this path under jit there); here, pin the form.
        import jax
        _force_engine(monkeypatch, "scatter")
        b = jnp.zeros((2, 64), jnp.int32)
        base = jnp.ones((3, 64), jnp.float32)
        for fn, args in (
                (lambda b, s: node_histogram(b, jnp.zeros(64, jnp.int32), s,
                                             1, 15), (b, base)),
                (lambda b, s: histogram_cols(b, s, 15), (b, base))):
            text = jax.jit(fn).lower(*args).as_text()
            assert "reduce_precision" in text
            assert "bf16" not in text


class TestTrainLevelEquivalence:
    """Same trees — not just same histograms — under every engine."""

    @staticmethod
    def _fit(quantized: bool):
        from mmlspark_tpu.models.gbdt.booster import train_booster
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        rng = np.random.default_rng(11)
        X = rng.normal(size=(3000, 6)).astype(np.float32)
        y = (X[:, 0] * X[:, 1] + 0.4 * X[:, 2] > 0).astype(np.float32)
        cfg = GrowConfig(num_leaves=15, min_data_in_leaf=10,
                         growth_policy="depthwise",
                         quantized_grad=quantized)
        return train_booster(X, y, objective="binary", num_iterations=4,
                             cfg=cfg, max_bin=63, bin_sample_count=3000,
                             seed=0), X

    @pytest.mark.parametrize("quantized", [False, True])
    def test_tree_structure_bit_identical(self, quantized, monkeypatch):
        structures = {}
        leaves = {}
        for engine in ENGINES:
            _force_engine(monkeypatch, engine)
            b, X = self._fit(quantized)
            structures[engine] = (np.asarray(b.trees.feat),
                                  np.asarray(b.trees.thr_bin),
                                  np.asarray(b.trees.left),
                                  np.asarray(b.trees.right),
                                  np.asarray(b.trees.is_leaf))
            leaves[engine] = np.asarray(b.trees.leaf_value)
        ref = structures["onehot"]
        for engine in ENGINES[1:]:
            for a, w in zip(structures[engine], ref):
                np.testing.assert_array_equal(a, w, err_msg=engine)
            # leaf values are f32 ratios of f32-accumulated sums: identical
            # split structure, equal to tight tolerance
            np.testing.assert_allclose(leaves[engine], leaves["onehot"],
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=engine)


class TestAutoSentinel:
    @pytest.mark.parametrize("rows", [300, 9000])
    def test_sweep_fast_path_eligible_at_any_row_count(self, rows):
        # default-config estimators take the vmapped sweep whatever the row
        # count (9000 rows used to fall back to sequential fits on the CPU,
        # where the removed subtraction fork would have engaged)
        from mmlspark_tpu.automl.sweep import _eligible, swept_fit
        from mmlspark_tpu.core.dataset import Dataset
        from mmlspark_tpu.models.gbdt.api import LightGBMClassifier

        est = LightGBMClassifier(numIterations=2, numLeaves=7,
                                 minDataInLeaf=2)
        maps = [{"learningRate": 0.1}, {"learningRate": 0.3}]
        assert _eligible(est, maps)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(rows, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        models = swept_fit(est, maps, Dataset({"features": X, "label": y}))
        assert models is not None and len(models) == 2

    @pytest.mark.parametrize("make", ["estimator", "GrowConfig"])
    def test_removed_subtraction_options_are_refused(self, make):
        # histSubtraction / compactSelector and their GrowConfig fields went
        # with the row-compaction fork (docs/migration.md): asking for them
        # is an error that names what was asked for, never a silent no-op
        from mmlspark_tpu.models.gbdt.api import LightGBMClassifier
        from mmlspark_tpu.models.gbdt.growth import GrowConfig
        if make == "estimator":
            with pytest.raises(AttributeError, match="histSubtraction"):
                LightGBMClassifier(histSubtraction=True)
            with pytest.raises(AttributeError, match="compactSelector"):
                LightGBMClassifier(compactSelector="argsort")
        else:
            with pytest.raises(TypeError, match="hist_subtraction"):
                GrowConfig(hist_subtraction=True)
            with pytest.raises(TypeError, match="compact_selector"):
                GrowConfig(compact_selector="argsort")

    def test_no_auto_in_step_cache_keys(self):
        # runtime version of the lint rule: fit with the defaults
        # (hist_blocks="auto") and prove no unresolved sentinel reached a
        # compiled-program key
        from mmlspark_tpu.models.gbdt import booster as B
        from mmlspark_tpu.models.gbdt.booster import train_booster

        rng = np.random.default_rng(0)
        X = rng.normal(size=(400, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        train_booster(X, y, objective="binary", num_iterations=2,
                      max_bin=15, bin_sample_count=400)
        assert B._STEP_CACHE, "fit built no cached programs?"
        bad = [k for k in B._STEP_CACHE if "'auto'" in repr(k)]
        assert not bad, bad


class TestHostLoopDonation:
    def test_donated_step_round_trips(self, monkeypatch):
        """The host round loop donates its scores/vscores buffers
        (donate_argnums) on accelerator backends: every iteration must
        still see the previous round's margins (use-after-donate raises,
        silent aliasing would corrupt the history), and the loop must
        match the fused single-dispatch path bit for bit. On the CPU
        backend donation is deliberately OFF (donating these sharded
        shard_map buffers corrupted the heap on jax 0.4.37 — see the
        booster.py comment), so here this test pins the gating plus the
        host-loop/fused equivalence the donation must preserve."""
        from mmlspark_tpu.models.gbdt.booster import train_booster
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        rng = np.random.default_rng(5)
        X = rng.normal(size=(2000, 5)).astype(np.float32)
        y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
        Xv, yv = X[:500], y[:500]
        kw = dict(objective="binary", num_iterations=6,
                  cfg=GrowConfig(num_leaves=7), max_bin=31,
                  bin_sample_count=2000, seed=0,
                  valid_set=(Xv, yv, None))
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_VALID", "1")
        b_host = train_booster(X, y, **kw)        # donated host loop
        monkeypatch.delenv("MMLSPARK_TPU_DISABLE_FUSED_VALID")
        b_fused = train_booster(X, y, **kw)       # single fused dispatch
        np.testing.assert_array_equal(np.asarray(b_host.predict_raw(X)),
                                      np.asarray(b_fused.predict_raw(X)))
        h1 = b_host.eval_history
        h2 = b_fused.eval_history
        assert list(h1) == list(h2)
        for k in h1:
            np.testing.assert_allclose(h1[k], h2[k], rtol=1e-6)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
