"""Unit tests for the histogram ops and device binning.

The MXU one-hot formulation and the fused node-histogram kernel are the hot
path of GBDT training (reference behavior: LightGBM's native histogram
construction behind LGBM_BoosterUpdateOneIter, lightgbm/TrainUtils.scala:246);
these tests pin them against a naive numpy scatter-add so layout/kernel
changes can't silently drift.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from mmlspark_tpu.ops.binning import QuantileBinner, bin_cols_device
from mmlspark_tpu.ops.histogram import (histogram, histogram_cols,
                                        node_histogram, quantize_stats)


def _naive_hist(binned, stats, B):
    n, F = binned.shape
    S = stats.shape[1]
    out = np.zeros((F, S, B), np.float64)
    sb = stats.astype(np.float32).astype(jnp.bfloat16).astype(np.float64)
    for r in range(n):
        for f in range(F):
            out[f, :, binned[r, f]] += sb[r]
    return out.astype(np.float32)


@pytest.mark.parametrize("S", [1, 3, 7])
def test_histogram_matches_naive(S):
    rng = np.random.default_rng(0)
    n, F, B = 257, 5, 19
    binned = rng.integers(0, B, size=(n, F), dtype=np.int32)
    stats = rng.normal(size=(n, S)).astype(np.float32)
    got = np.asarray(histogram(jnp.asarray(binned), jnp.asarray(stats), B))
    want = _naive_hist(binned, stats, B)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-3)


def test_histogram_cols_equals_row_major():
    rng = np.random.default_rng(1)
    n, F, B, S = 200, 4, 16, 6
    binned = rng.integers(0, B, size=(n, F), dtype=np.int32)
    stats = rng.normal(size=(n, S)).astype(np.float32)
    a = np.asarray(histogram(jnp.asarray(binned), jnp.asarray(stats), B))
    b = np.asarray(histogram_cols(jnp.asarray(binned.T),
                                  jnp.asarray(stats.T), B))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("W", [1, 2, 5])
def test_node_histogram_matches_masked_stats(W):
    """Fused node scatter == explicit per-node masked stats histogram."""
    rng = np.random.default_rng(2)
    n, F, B = 301, 6, 23
    binned = rng.integers(0, B, size=(n, F), dtype=np.int32)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    mask = (rng.uniform(size=n) < 0.9).astype(np.float32) * \
        rng.choice([1.0, 2.5], size=n).astype(np.float32)  # GOSS-style amp
    pos = rng.integers(-1, W, size=n).astype(np.int32)

    base = np.stack([grad * mask, hess * mask, mask], axis=0)
    got = np.asarray(node_histogram(jnp.asarray(binned.T), jnp.asarray(pos),
                                    jnp.asarray(base), W, B))
    assert got.shape == (F, 3 * W, B)
    explicit = np.stack(
        [np.where(pos == w, base[s], 0.0) for w in range(W) for s in range(3)],
        axis=1)  # [n, 3W]
    want = _naive_hist(binned, explicit, B)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-3)


def test_bin_cols_device_matches_native():
    rng = np.random.default_rng(3)
    n, F = 500, 7
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[rng.uniform(size=X.shape) < 0.05] = np.nan
    binner = QuantileBinner(max_bin=31, sample_count=400, seed=0).fit(X)
    host = binner.transform(X)                       # native/searchsorted path
    dev = np.asarray(bin_cols_device(jnp.asarray(X),
                                     jnp.asarray(binner.upper_bounds)))
    np.testing.assert_array_equal(host.T, dev)


def test_bin_cols_device_boundary_equality():
    """x exactly equal to an upper bound lands in that bound's bin (left)."""
    ub = np.array([[1.0, 2.0, 3.0]], dtype=np.float32)
    X = np.array([[0.5], [1.0], [2.0], [3.0], [3.5]], dtype=np.float32)
    dev = np.asarray(bin_cols_device(jnp.asarray(X), jnp.asarray(ub)))[0]
    host = np.searchsorted(ub[0], X[:, 0], side="left")
    np.testing.assert_array_equal(dev, host)


class TestPallasInterpret:
    """Run the REAL Pallas kernels through the interpreter on CPU so the
    packed-feature layouts are validated without TPU hardware."""

    @pytest.fixture(autouse=True)
    def _interp(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")

    @pytest.mark.parametrize("B", [255, 63, 31])   # P = 1, 2, 4
    def test_kernel_matches_xla_fallback(self, B, monkeypatch):
        rng = np.random.default_rng(0)
        n, F, S = 1200, 5, 6
        binned_t = jnp.asarray(
            rng.integers(0, B, size=(F, n), dtype=np.int32))
        stats_t = jnp.asarray(rng.normal(size=(S, n)).astype(np.float32))
        got = np.asarray(histogram_cols(binned_t, stats_t, B))
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1")
        want = np.asarray(histogram_cols(binned_t, stats_t, B))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    # (63, 16): the width batched leafwise emits (leaf_batch=8 -> 2*KB)
    @pytest.mark.parametrize("B,W", [(255, 3), (63, 4), (31, 2), (63, 16)])
    def test_node_kernel_matches_xla_fallback(self, B, W, monkeypatch):
        rng = np.random.default_rng(1)
        n, F = 1100, 6
        binned_t = jnp.asarray(
            rng.integers(0, B, size=(F, n), dtype=np.int32))
        pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
        base = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
        got = np.asarray(node_histogram(binned_t, pos, base, W, B))
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1")
        want = np.asarray(node_histogram(binned_t, pos, base, W, B))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


    # the folded layout's gate (ops/histogram.py::_fold_words): two
    # 128-lane tiles of bins and both copies of the stats in 128 rows
    LAYOUTS = [(255, 1, "folded"), (255, 16, "folded"), (255, 21, "folded"),
               (129, 16, "folded"), (255, 22, "plain"), (255, 31, "plain"),
               (128, 16, "plain"), (63, 16, "plain")]

    @staticmethod
    def _layout_case(B, W, bins, n=1100, F=6):
        """Bins at the fold's seams (127 | 128, the last bin) and a column
        that is one bin throughout, beside uniform ones."""
        rng = np.random.default_rng(B * 100 + W)
        b = rng.integers(0, B, size=(F, n), dtype=np.int32)
        b[0, :] = min(128, B - 1)
        b[1, :n // 3] = min(127, B - 1)
        b[1, n // 3:2 * n // 3] = min(128, B - 1)
        b[1, 2 * n // 3:] = B - 1                    # 254 at 255 bins
        pos = rng.integers(-1, W, size=n).astype(np.int32)
        grad = rng.normal(size=n).astype(np.float32)
        mask = (rng.uniform(size=n) < 0.9).astype(np.float32)
        base = np.stack([grad * mask, np.abs(grad) * mask, mask])
        return (jnp.asarray(b.astype(bins)), jnp.asarray(pos),
                jnp.asarray(base))

    @staticmethod
    def _assert_matches_scatter(case, W, B, stats, monkeypatch):
        """The interpreted kernel against the scatter engine on one case:
        int8 to the bit, bf16 to tests/test_histogram_engines.py's
        tolerance with the count channel exact."""
        binned_t, pos, base = case
        stats_t, scales = (quantize_stats(base) if stats == "int8"
                           else (base, None))
        got = np.asarray(node_histogram(binned_t, pos, stats_t, W, B,
                                        scales=scales))
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET")
        monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "scatter")
        want = np.asarray(node_histogram(binned_t, pos, stats_t, W, B,
                                         scales=scales))
        assert got.shape == (binned_t.shape[0], 3 * W, B)
        if stats == "int8":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_array_equal(got[:, 2::3, :], want[:, 2::3, :])
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @staticmethod
    def _staged_counts(name, label, values, case, W, B, stats):
        """What staging one node kernel out adds to a labelled counter."""
        import jax

        from mmlspark_tpu.observability import metrics

        def counts():
            return {v: metrics.counter(name, **{label: v}).value
                    for v in values}

        binned_t, pos, base = case
        stats_t, scales = (quantize_stats(base) if stats == "int8"
                           else (base, None))
        before = counts()
        jax.jit(lambda b, p, s: node_histogram(
            b, p, s, W, B, scales=scales)).lower(binned_t, pos, stats_t)
        return {v: n - before[v] for v, n in counts().items()}

    @pytest.mark.parametrize("bins", ["uint8", "int32"])
    @pytest.mark.parametrize("stats", ["int8", "bf16"])
    @pytest.mark.parametrize("B,W,layout", LAYOUTS)
    def test_node_kernel_layouts_match_scatter(self, B, W, layout, stats,
                                               bins, monkeypatch):
        """Either layout returns the scatter engine's histogram."""
        self._assert_matches_scatter(self._layout_case(B, W, bins), W, B,
                                     stats, monkeypatch)

    @pytest.mark.parametrize("stats", ["int8", "bf16"])
    @pytest.mark.parametrize("B,W,layout", LAYOUTS)
    def test_layout_counter_follows_the_gate(self, B, W, layout, stats):
        """hist_kernel_layout_total{layout} says which layout a staged-out
        kernel took: a function of (B, S, dtype) and nothing else."""
        delta = self._staged_counts(
            "hist_kernel_layout_total", "layout", ("folded", "plain"),
            self._layout_case(B, W, "uint8", n=512, F=2), W, B, stats)
        assert delta == {"folded": 0, "plain": 0, layout: 1}

    # the tile builder's gate (ops/histogram.py::_onehot_packed): int8
    # statistics and one 128-sublane tile, so every plain kernel up to 128
    # bins (P = 2, 2, 2, 4, 8, 16, 1, 1 features a tile) and every folded one
    ONEHOTS = [(63, 1), (63, 16), (64, 16), (31, 4), (16, 4), (8, 2),
               (100, 16), (128, 16)]

    @staticmethod
    def _onehot_case(B, W, bins, n=1100, F=7):
        """An odd feature count (the last group's padding feature bins to
        0) and columns pinned to bin 0 and to B - 1, which is the tile's
        BP - 1 where B == BP: the seam between two packed features."""
        binned_t, pos, base = TestPallasInterpret._layout_case(
            B, W, "int32", n=n, F=F)
        b = np.array(binned_t)
        b[0, :] = 0
        b[1, :] = B - 1
        b[2, 0::2], b[2, 1::2] = 0, B - 1
        b[F - 1, :] = B - 1                          # beside the padding
        return jnp.asarray(b.astype(bins)), pos, base

    @pytest.mark.parametrize("bins", ["uint8", "int32"])
    @pytest.mark.parametrize("stats", ["int8", "bf16"])
    @pytest.mark.parametrize("B,W", ONEHOTS)
    def test_node_kernel_onehots_match_scatter(self, B, W, stats, bins,
                                               monkeypatch):
        """Packed or compared, a tile gives the scatter engine's
        histogram."""
        self._assert_matches_scatter(self._onehot_case(B, W, bins), W, B,
                                     stats, monkeypatch)

    @pytest.mark.parametrize("stats", ["int8", "bf16"])
    @pytest.mark.parametrize("B,W", ONEHOTS + [(255, 16), (255, 22),
                                               (300, 4)])
    def test_onehot_counter_follows_the_gate(self, B, W, stats):
        """hist_kernel_onehot_total{onehot} says which build a staged-out
        kernel's tiles got: a function of (stats dtype, BP, P), where the
        folded layout's tile is BP = 128, P = 1."""
        bins = "uint8" if B <= 256 else "int32"
        delta = self._staged_counts(
            "hist_kernel_onehot_total", "onehot", ("packed", "compare"),
            self._onehot_case(B, W, bins, n=512, F=3), W, B, stats)
        # every tile value under 128: up to 128 bins plain, or folded
        one_tile = B <= 128 or (B <= 256 and 3 * W <= 64)
        onehot = "packed" if stats == "int8" and one_tile else "compare"
        assert delta == {"packed": 0, "compare": 0, onehot: 1}

    @pytest.mark.parametrize("B,S,layout", [(255, 6, "folded"),
                                            (200, 64, "folded"),
                                            (255, 66, "plain")])
    def test_cols_kernel_layouts_match_scatter(self, B, S, layout,
                                               monkeypatch):
        """histogram_cols (gbdt_hist_kernel) takes the same gate."""
        from mmlspark_tpu.observability import metrics
        rng = np.random.default_rng(S)
        n, F = 1200, 4
        b = rng.integers(0, B, size=(F, n), dtype=np.int32)
        b[0, :] = 128
        b[1, :n // 2], b[1, n // 2:] = 127, B - 1
        binned_t = jnp.asarray(b)
        stats_t = jnp.asarray(rng.normal(size=(S, n)).astype(np.float32))
        taken = metrics.counter("hist_kernel_layout_total", layout=layout)
        before = taken.value
        got = np.asarray(histogram_cols(binned_t, stats_t, B))
        assert taken.value == before + 1
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET")
        monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "scatter")
        want = np.asarray(histogram_cols(binned_t, stats_t, B))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("B", [63, 128])
    def test_cols_kernel_int8_stats_pack(self, B):
        """histogram_cols with int8 statistics takes the packed build too,
        and sums exactly."""
        from mmlspark_tpu.observability import metrics
        rng = np.random.default_rng(B)
        n, F, S = 1200, 5, 6
        b = rng.integers(0, B, size=(F, n), dtype=np.int32)
        b[0, :], b[F - 1, :] = 0, B - 1
        st = rng.integers(-20, 20, size=(S, n)).astype(np.float32)
        packed = metrics.counter("hist_kernel_onehot_total", onehot="packed")
        before = packed.value
        got = np.asarray(histogram_cols(jnp.asarray(b), jnp.asarray(st), B,
                                        stats_dtype=jnp.int8))
        assert packed.value == before + 1
        want = np.zeros((F, S, B), np.float32)
        for f in range(F):
            for s_ in range(S):
                np.add.at(want[f, s_], b[f], st[s_])
        np.testing.assert_array_equal(got, want)


class TestNarrowBinStorage:
    """uint8/int16 bin-id storage (the Criteo-scale HBM lever): the Pallas
    kernels widen per block in VMEM, so results must be bit-identical to
    int32 storage through the interpreter AND the XLA fallback."""

    @pytest.fixture(autouse=True)
    def _interp(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")

    @pytest.mark.parametrize("dtype", ["uint8", "int16"])
    @pytest.mark.parametrize("B,W", [(255, 3), (63, 16)])
    def test_node_kernel_narrow_matches_int32(self, dtype, B, W):
        rng = np.random.default_rng(5)
        n, F = 1100, 6
        b32 = rng.integers(0, B, size=(F, n), dtype=np.int32)
        pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
        base = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
        got = np.asarray(node_histogram(
            jnp.asarray(b32.astype(dtype)), pos, base, W, B))
        want = np.asarray(node_histogram(jnp.asarray(b32), pos, base, W, B))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("dtype", ["uint8", "int16"])
    def test_xla_fallback_narrow_matches_int32(self, dtype, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1")
        rng = np.random.default_rng(6)
        n, F, S, B = 900, 4, 6, 255
        b32 = rng.integers(0, B, size=(F, n), dtype=np.int32)
        stats_t = jnp.asarray(rng.normal(size=(S, n)).astype(np.float32))
        got = np.asarray(histogram_cols(
            jnp.asarray(b32.astype(dtype)), stats_t, B))
        want = np.asarray(histogram_cols(jnp.asarray(b32), stats_t, B))
        np.testing.assert_array_equal(got, want)


class TestQuantizedHistogram:
    """int8 quantized-gradient histograms (LightGBM use_quantized_grad)."""

    def test_quantize_dequantize_bounds(self):
        rng = np.random.default_rng(0)
        base = rng.normal(size=(3, 500)).astype(np.float32) * \
            np.array([[5.0], [0.25], [1.0]], np.float32)
        q, scales = quantize_stats(jnp.asarray(base))
        assert q.dtype == jnp.int8
        err = np.abs(np.asarray(q) * np.asarray(scales)[:, None] - base)
        # round-to-nearest: error bounded by half a quantization step
        assert (err <= 0.5 * np.asarray(scales)[:, None] + 1e-7).all()

    def test_quantized_node_histogram_matches_int_reference(self):
        rng = np.random.default_rng(1)
        n, F, B, W = 700, 4, 31, 3
        binned = rng.integers(0, B, size=(F, n), dtype=np.int32)
        pos = rng.integers(-1, W, size=n).astype(np.int32)
        base = rng.normal(size=(3, n)).astype(np.float32)
        q, scales = quantize_stats(jnp.asarray(base))
        got = np.asarray(node_histogram(jnp.asarray(binned),
                                        jnp.asarray(pos), q, W, B,
                                        scales=scales))
        # exact integer reference, dequantized
        qn = np.asarray(q).astype(np.int64)
        want = np.zeros((F, 3 * W, B), np.int64)
        for r in range(n):
            if pos[r] < 0:
                continue
            for f in range(F):
                for s_ in range(3):
                    want[f, pos[r] * 3 + s_, binned[f, r]] += qn[s_, r]
        want = want * np.asarray(scales)[np.tile(np.arange(3), W)][None, :,
                                                                  None]
        np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-6,
                                   atol=1e-6)

    def test_quantized_kernel_interpret_matches_xla(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.default_rng(2)
        n, F, B, W = 1100, 5, 63, 4
        binned = jnp.asarray(rng.integers(0, B, size=(F, n), dtype=np.int32))
        pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
        base = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
        q, scales = quantize_stats(base)
        got = np.asarray(node_histogram(binned, pos, q, W, B, scales=scales))
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1")
        want = np.asarray(node_histogram(binned, pos, q, W, B, scales=scales))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_quantized_training_quality(self):
        """use_quantized_grad stays within ~1% accuracy of full precision."""
        from mmlspark_tpu.models.gbdt.booster import train_booster
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        rng = np.random.default_rng(3)
        X = rng.normal(size=(3000, 8)).astype(np.float32)
        y = ((X[:, 0] * X[:, 1] + 0.5 * X[:, 2]) > 0).astype(np.float32)
        accs = {}
        for quant in (False, True):
            cfg = GrowConfig(num_leaves=15, min_data_in_leaf=5,
                             growth_policy="depthwise", quantized_grad=quant)
            b = train_booster(X, y, objective="binary", num_iterations=15,
                              cfg=cfg, max_bin=63, bin_sample_count=3000)
            accs[quant] = ((b.predict(X) > 0.5) == y).mean()
        assert accs[True] >= accs[False] - 0.01, accs

    def test_quantized_pure_interaction_recovers(self):
        """On a pure-interaction target every root-level gain is noise, so
        int8-quantized split selection starts noisier. quant_warmup_iters
        (full-precision first iterations) removes the early lag: accuracy
        must match full precision from iteration count 5 on, not just after
        ~15-iteration recovery."""
        from mmlspark_tpu.models.gbdt.booster import train_booster
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        rng = np.random.default_rng(0)
        X = rng.normal(size=(8000, 10)).astype(np.float32)
        y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
        for iters in (5, 15):
            accs = {}
            for quant in (False, True):
                cfg = GrowConfig(num_leaves=15, growth_policy="depthwise",
                                 quantized_grad=quant)
                b = train_booster(X, y, objective="binary",
                                  num_iterations=iters, cfg=cfg, max_bin=63)
                accs[quant] = ((b.predict(X) > 0.5) == y).mean()
            assert accs[True] >= accs[False] - 0.02, (iters, accs)

    def test_quantized_parity_realistic_scale(self):
        """The fast config IS the parity config: 120 iterations, leafwise,
        max_bin=255 — quantized-vs-full train AUC within the reference
        benchmark tolerance (benchmarks_VerifyLightGBMClassifier.csv pins
        AUC to ~1e-2 across environments; we use 5e-3)."""
        from sklearn.datasets import load_breast_cancer
        from sklearn.metrics import roc_auc_score
        from mmlspark_tpu.models.gbdt.booster import train_booster
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        d = load_breast_cancer()
        X = d.data.astype(np.float32)
        rng = np.random.default_rng(7)
        # interaction-contaminated target: real labels XOR a pure product
        # term, so early-split noise has something to get wrong
        flip = (X[:, 0] - X[:, 0].mean()) * (X[:, 1] - X[:, 1].mean()) > 0
        y = np.where(rng.random(len(X)) < 0.25,
                     (d.target != flip).astype(np.float32),
                     d.target.astype(np.float32))
        aucs = {}
        for quant in (False, True):
            cfg = GrowConfig(num_leaves=31, growth_policy="leafwise",
                             quantized_grad=quant)
            b = train_booster(X, y, objective="binary", num_iterations=120,
                              cfg=cfg, max_bin=255, bin_sample_count=600)
            aucs[quant] = roc_auc_score(y, np.asarray(b.predict(X)))
        assert aucs[True] >= aucs[False] - 5e-3, aucs

    def test_quantized_renew_leaf_and_warmup_knobs(self):
        """quant_renew_leaf=False / quant_warmup_iters=0 restore the raw
        int8 path (distinct models), and warmup iterations reproduce the
        full-precision trees exactly (same PRNG stream, same structure)."""
        from mmlspark_tpu.models.gbdt.booster import train_booster
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        rng = np.random.default_rng(5)
        X = rng.normal(size=(2000, 6)).astype(np.float32)
        y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
        base = dict(num_leaves=7, growth_policy="leafwise")

        # a 2-iteration fit fully inside warmup == the full-precision fit
        bq = train_booster(X, y, objective="binary", num_iterations=2,
                           cfg=GrowConfig(quantized_grad=True,
                                          quant_warmup_iters=2, **base),
                           max_bin=63)
        bf = train_booster(X, y, objective="binary", num_iterations=2,
                           cfg=GrowConfig(quantized_grad=False, **base),
                           max_bin=63)
        np.testing.assert_array_equal(np.asarray(bq.predict_raw(X)),
                                      np.asarray(bf.predict_raw(X)))

        # knobs off -> the raw quantized path (differs from renewed+warm)
        b_raw = train_booster(X, y, objective="binary", num_iterations=8,
                              cfg=GrowConfig(quantized_grad=True,
                                             quant_renew_leaf=False,
                                             quant_warmup_iters=0, **base),
                              max_bin=63)
        b_def = train_booster(X, y, objective="binary", num_iterations=8,
                              cfg=GrowConfig(quantized_grad=True, **base),
                              max_bin=63)
        assert not np.array_equal(np.asarray(b_raw.predict_raw(X)),
                                  np.asarray(b_def.predict_raw(X)))


def test_wide_feature_fori_path_matches_xla(monkeypatch):
    """Above _UNROLL_MAX feature groups the kernel keeps a dynamic loop;
    pin the wide path against the XLA fallback through the interpreter."""
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
    import importlib

    from mmlspark_tpu.ops import histogram as H
    importlib.reload(H)
    try:
        rng = np.random.default_rng(0)
        F, n, B, W = 130, 512, 255, 3    # P=1: 130 groups > _UNROLL_MAX
        assert F // H._bin_packing(B)[1] > H._unroll_max()
        bt = jnp.asarray(rng.integers(0, B, (F, n)), dtype=jnp.int32)
        pos = jnp.asarray(rng.integers(-1, W, n), dtype=jnp.int32)
        base = jnp.asarray(rng.normal(size=(3, n)).astype(np.float32))
        got = np.asarray(H.node_histogram(bt, pos, base, W, B))
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1")
        importlib.reload(H)
        want = np.asarray(H.node_histogram(bt, pos, base, W, B))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    finally:
        monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET")
        monkeypatch.delenv("MMLSPARK_TPU_DISABLE_PALLAS_HIST", raising=False)
        importlib.reload(H)


def test_vmem_picker_fits_bench_shapes_at_leafbatch_width():
    """The TPU kernel must actually engage (row block > 0) at the bench
    shape for every width the growth paths emit — a silent XLA fallback
    would be ~10x slower and invisible on CPU."""
    from mmlspark_tpu.ops.histogram import _pick_row_block

    # the smoke's 1 M x 28 and the benchmark cells' 68,321,280 x 39
    # (BENCHMARK.json: one chip's share of Criteo-1TB, uint8 bins)
    for n, F, widths in ((1_000_000, 28, (1, 2, 16, 31)),
                         (68_321_280, 39, (1, 16))):
        for B in (255, 63):
            for W in widths:
                rb = _pick_row_block(n, F, 3 * W, B, fused_w=W)
                assert rb > 0, (n, B, W)
                rbq = _pick_row_block(n, F, 3 * W, B, fused_w=W,
                                      quantized=True)
                assert rbq > 0, ("quantized", n, B, W)
                # a pass is 16,680 or 8,340 grid steps at the cells' size:
                # nothing smaller than 4096 rows a step belongs there
                assert min(rb, rbq) >= 4096, (n, B, W, rb, rbq)
                # the cells' own kernels (int8, W = 1 and 16: folded at 255
                # bins, packed-word tiles at 63) take the largest block
                if n > 1_000_000:
                    assert rbq == 8192, (n, B, W, rbq)
