"""GBDT gradient histograms — backend-adaptive engine dispatch.

The reference delegates histogram building to LightGBM's C++ (CUDA/CPU) kernels
behind LGBM_BoosterUpdateOneIter (reference: lightgbm/TrainUtils.scala:246).
Here ONE resolver (:func:`resolve_engine`, ``MMLSPARK_TPU_HIST_ENGINE``)
picks the formulation the current backend actually lowers well — all three
produce equal histograms through the same entry points (int8 statistics
exactly, in int32; float ones to f32 accumulation tolerance, which is a
cell's own: every ``(feature, stat, bin)`` cell is summed by itself over
the row blocks, so it is good to a few 1e-6 of its own value at 8340
blocks, 68 M rows, whatever its neighbours hold, and a count is exact up
to 2^24 rows a cell. What a caller may not do is subtract two such sums of
the root's size and expect a leaf's: growth.py sums each side of a split
from its own cells; PERF.md, PR 33; docs/performance.md "Histogram engine
selection"):

  * ``pallas`` — the TPU kernels below (one-hot in VMEM, MXU contraction);
  * ``onehot`` — the XLA one-hot-matmul fallback below (MXU-shaped, used
    on TPU for shapes the kernel can't tile);
  * ``scatter`` — :mod:`.histogram_scatter`'s segment-sum scatter-adds
    (CPU/GPU: no ``[n, B]`` one-hot transient at all).

TPUs have no fast scatter-add, so the TPU-native formulation turns the
bin-scatter into dense one-hot contractions that run on the systolic array:

    hist[f, s, b] = sum_r stats[r, s] * (binned[r, f] == b)

i.e. per feature a ``[S, n] @ [n, B]`` matmul with the one-hot bin matrix.
Stats ride in bf16 (one-hot products are exact; values round at 2^-8 relative)
and accumulate in f32 on the MXU. The Pallas kernels run that contraction in
one of two layouts, picked from the static shape (:func:`_fold_words`): the
plain one above, or, at 129..256 bins with up to 64 stats rows, a FOLDED one
that splits a bin into ``hi * 128 + lo``, one-hots ``lo`` alone and lets
``hi`` pick one of two stacked copies of the stats — one MXU tile a feature
instead of two, the same histogram cell for cell.

Layout: everything here is **column-major** — ``binned_t`` is ``[F, n]`` and
stats are ``[S, n]`` — so the Pallas grid slices the row axis (the 128-lane
axis) directly with no per-call transposes. Training materializes ``binned_t``
once; the per-level inputs are then tiny ([n] node positions + [3, n] stats).

``node_histogram`` is the fused training entry point: tree growth needs
``hist[f, w, s, b]`` for every frontier node ``w``; instead of materializing
the ``[3W, n]`` masked-stats matrix in HBM, the kernel rebuilds it per row
block in VMEM from the row->frontier-position vector and the shared
(grad, hess, count) stats.

Under ``shard_map`` with rows sharded over the ``data`` mesh axis, callers
``psum`` the result — that single collective replaces the reference's entire
TCP ring all-reduce (LGBM_NetworkInit, TrainUtils.scala:496-512).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram_scatter import hist_scatter, node_hist_scatter

# one-hot transient element budget per chunk (bf16 elements); ~64M ≈ 128 MB
_ONEHOT_BUDGET = 64 * 1024 * 1024


def _on_tpu_device() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_mode() -> bool:
    """``MMLSPARK_TPU_PALLAS_INTERPRET``: run the kernels through the Pallas
    interpreter so packing/layout bugs surface on a CPU test box. On a TPU
    backend the interpreter would stand in for the Mosaic kernel unnoticed,
    so there the variable is an error, not a mode."""
    if not os.environ.get("MMLSPARK_TPU_PALLAS_INTERPRET"):
        return False
    if _on_tpu_device():
        raise RuntimeError(
            "MMLSPARK_TPU_PALLAS_INTERPRET is set on a TPU backend: the "
            "histogram kernels would run interpreted instead of compiled "
            "by Mosaic; unset it")
    return True


def _use_pallas() -> bool:
    if os.environ.get("MMLSPARK_TPU_DISABLE_PALLAS_HIST"):
        return False
    return _interpret_mode() or _on_tpu_device()


# ---------------------------------------------------------------------------
# Engine resolution: pallas (TPU MXU kernel) / onehot (XLA one-hot matmul —
# the MXU-shaped fallback) / scatter (flattened segment-sum scatter-adds —
# what XLA CPU/GPU lowers well). One resolver, three engines, identical
# results through the same entry points (int8 exactly; float to each cell's
# own f32 accumulation tolerance, see the module docstring) — so `growth.py`
# never cares which ran.
# ---------------------------------------------------------------------------

_ENGINES = ("pallas", "onehot", "scatter")


def resolve_engine() -> str:
    """Histogram engine for the current backend/env (before shape gates).

    ``MMLSPARK_TPU_HIST_ENGINE=pallas|onehot|scatter|auto`` (default auto):
    ``auto`` picks ``pallas`` where the TPU kernel can lower (a TPU
    backend, or ``MMLSPARK_TPU_PALLAS_INTERPRET`` off-TPU) and ``scatter``
    elsewhere: a function of the environment and the backend alone, and the
    one place the engine is chosen. An explicit ``pallas`` where the kernel
    cannot lower (no TPU backend and no interpreter, or
    ``MMLSPARK_TPU_DISABLE_PALLAS_HIST`` set) raises: a pinned engine never
    silently becomes another one.
    """
    env = (os.environ.get("MMLSPARK_TPU_HIST_ENGINE") or "auto")
    env = env.strip().lower() or "auto"
    if env not in _ENGINES + ("auto",):
        raise ValueError(
            f"MMLSPARK_TPU_HIST_ENGINE must be one of "
            f"{('auto',) + _ENGINES}, got {env!r}")
    if env == "pallas":
        if not _use_pallas():
            raise RuntimeError(
                "MMLSPARK_TPU_HIST_ENGINE=pallas but the Pallas TPU kernel "
                f"cannot lower here: backend {jax.default_backend()!r} with "
                "the interpreter off, or MMLSPARK_TPU_DISABLE_PALLAS_HIST set")
        return "pallas"
    if env != "auto":
        return env
    if _use_pallas():
        return "pallas"
    return "onehot" if _on_tpu_device() else "scatter"


def round_stats(stats: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """The input rounding every engine applies to float stats, for code that
    keeps them in f32 afterwards (the scatter engine, growth's node totals).
    ``reduce_precision`` and not an ``astype`` round trip: inside one program
    XLA on TPU elides ``f32 -> bf16 -> f32``, and the sums would then be of
    unrounded stats — a different histogram from the one the MXU engines
    build, and one that no longer adds up to ``growth._stat_totals``."""
    fi = jnp.finfo(dtype)
    return lax.reduce_precision(stats, exponent_bits=fi.nexp,
                                mantissa_bits=fi.nmant)


def _count_build(name: str, **labels) -> None:
    """Bump a counter of what a program was BUILT with: these choices are
    static per compiled program and made at trace time, so the counters
    track program builds, not per-batch executions."""
    try:
        from ..observability import metrics as _metrics
        _metrics.safe_counter(name, **labels).inc()
    except Exception:  # noqa: BLE001 — telemetry must not fail the kernel
        pass


def _note_engine(engine: str) -> None:
    """hist_engine_selected_total{engine}: the engine a program took."""
    _count_build("hist_engine_selected_total", engine=engine)


def _select_engine(n: int, F: int, S: int, B: int, fused_w: int = 0,
                   quantized: bool = False) -> str:
    """Resolved engine with the Pallas shape gate applied: shapes the
    kernel cannot tile within the VMEM budget fall to the one-hot matmul.
    On a TPU that costs an HBM-resident one-hot per pass, so the fall is
    loud there — a flight event and a warning naming the shape."""
    eng = resolve_engine()
    if eng == "pallas" and _pick_row_block(n, F, S, B, fused_w=fused_w,
                                           quantized=quantized) <= 0:
        eng = "onehot"
        if _on_tpu_device():
            from ..observability import flight as _flight
            from ..observability import logging as _logging
            shape = dict(n=n, F=F, S=S, B=B, W=fused_w, quantized=quantized,
                         vmem_budget_bytes=_vmem_budget())
            _flight.record("hist_engine", event="pallas_shape_gate",
                           engine=eng, **shape)
            _logging.get_logger(__name__).warning(
                "Pallas histogram kernel cannot tile this shape within its "
                "VMEM budget; falling to the XLA one-hot engine", **shape)
    _note_engine(eng)
    return eng


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def histogram(binned: jnp.ndarray, stats: jnp.ndarray, num_bins: int,
              stats_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Row-major convenience wrapper: ``[n, F]`` bins + ``[n, S]`` stats.

    Transposes and delegates to :func:`histogram_cols`. Training code should
    use the column-major entry points directly and hoist the ``binned``
    transpose out of the per-level loop.
    """
    return histogram_cols(jnp.transpose(binned), jnp.transpose(stats),
                          num_bins, stats_dtype)


def histogram_cols(binned_t: jnp.ndarray, stats_t: jnp.ndarray, num_bins: int,
                   stats_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Compute ``[F, S, B]`` histogram of per-row stats over feature bins.

    binned_t: [F, n] bin indices in [0, num_bins) — int32, int16 or uint8
        (narrow storage is widened per block in VMEM, never in HBM)
    stats_t:  [S, n] float stats (e.g. grad, hess, count-mask)
    Returns [F, S, B] float32.
    """
    F, n = binned_t.shape
    S = stats_t.shape[0]
    B = int(num_bins)
    # stats round to stats_dtype (bf16 default) on EVERY engine — scatter
    # included — so engine choice never changes the values being summed,
    # only the (f32) accumulation order
    eng = _select_engine(n, F, S, B)
    if eng == "scatter":
        # accumulates in f32, so the rounding must survive the widening
        return hist_scatter(binned_t, round_stats(stats_t, stats_dtype), B)
    stats_t = stats_t.astype(stats_dtype)
    if eng == "pallas":
        return _hist_pallas(binned_t, stats_t, B)
    return _hist_xla(binned_t, stats_t, B)


def quant_q_max(rows: int) -> float:
    """THE int8 quantization target for ``rows`` accumulated stats: shrinks
    below 127 once a histogram cell could overflow the int32 accumulator
    (q_max * rows must stay under 2^31). One definition shared by the
    plain path (rows = the local shard) and the deterministic blocked path
    (rows = rows-per-block) — if the accumulator ever widens, both paths
    move together or the bit-identity contract silently breaks."""
    return float(max(1, min(127, (2 ** 31 - 1) // max(int(rows), 1))))


def quantize_stats(base_t: jnp.ndarray, key=None, *, amax=None, q_max=None,
                   u=None):
    """Per-row-stat int8 quantization (LightGBM quantized training,
    use_quantized_grad): symmetric per-channel scale, stochastic rounding
    when a PRNG key is given (round-to-nearest otherwise). Returns
    (int8 stats [S, n], f32 scales [S]); dequantized histogram =
    int_hist * scale. int8 one-hot contractions run the MXU at 2x bf16
    throughput on v5e+.

    The quantization target shrinks below 127 for shards so large that a
    histogram cell could overflow the int32 accumulator (q_max * n must
    stay under 2^31): giant shards trade precision gracefully instead of
    wrapping negative.

    ``amax`` / ``q_max`` / ``u`` override the locally-derived scale
    maximum, accumulator bound and stochastic-rounding uniforms — the
    deterministic blocked-reduction path (growth.GrowConfig.hist_blocks)
    supplies GLOBAL values so every mesh topology quantizes each row
    identically."""
    n = base_t.shape[1]
    if q_max is None:
        q_max = quant_q_max(n)
    if amax is None:
        amax = jnp.max(jnp.abs(base_t), axis=1)
    scales = jnp.where(amax > 0, amax / q_max, 1.0)
    x = base_t / scales[:, None]
    if u is None and key is not None:
        u = jax.random.uniform(key, base_t.shape)
    q = jnp.floor(x + u) if u is not None else jnp.round(x)
    return jnp.clip(q, -q_max, q_max).astype(jnp.int8), scales


def node_histogram(binned_t: jnp.ndarray, row_pos: jnp.ndarray,
                   base_t: jnp.ndarray, num_nodes: int,
                   num_bins: int, scales=None) -> jnp.ndarray:
    """Per-frontier-node histograms in one fused pass: ``[F, W*3, B]``
    f32; the int8 sums of :func:`node_histogram_sums` dequantized by
    ``scales`` (:func:`dequantize_node_histogram`), the float ones as they
    are."""
    out = node_histogram_sums(binned_t, row_pos, base_t, num_nodes, num_bins,
                              quantized=scales is not None)
    return out if scales is None else dequantize_node_histogram(out, scales)


def dequantize_node_histogram(sums: jnp.ndarray, scales) -> jnp.ndarray:
    """``[..., W*3, B]`` int32 sums of int8 statistics to f32, each channel
    by its own scale of :func:`quantize_stats`."""
    chan_scale = scales[jnp.arange(sums.shape[-2]) % 3]
    return sums.astype(jnp.float32) * chan_scale[None, :, None]


def node_histogram_sums(binned_t: jnp.ndarray, row_pos: jnp.ndarray,
                        base_t: jnp.ndarray, num_nodes: int, num_bins: int,
                        quantized: bool = False) -> jnp.ndarray:
    """Per-frontier-node sums in one fused pass: ``[F, W*3, B]``, f32 for
    float statistics and, ``quantized``, the exact int32 sums of int8 ones,
    still to be scaled. A sum over a subset of rows is then the sum over all
    of them less the sum over the rest, to the bit: leafwise growth takes
    one child of a split from a pass and the other from its parent
    (``models.gbdt.growth.grow_tree``).

    binned_t: [F, n] int32/int16/uint8; row_pos: [n] int32 in [-1, W) — each row's
    position in the frontier (-1: row is at a finished leaf, contributes
    nothing); base_t: [3, n] f32 (grad*mask, hess*mask, mask).

    Channel layout matches ``stack([g*m_w, h*m_w, m_w for w])``:
    ``out[f, w*3 + s, b]`` is stat ``s`` of frontier node ``w``.

    On TPU the row->node one-hot and the masked stats never touch HBM: the
    Pallas kernel rebuilds them per row block in VMEM (the HBM inputs per
    level are just binned_t + [n] positions + [3, n] stats, vs the
    [3W, n] materialization the XLA fallback does).

    ``quantized`` (with int8 ``base_t`` from :func:`quantize_stats`) switches
    to quantized-gradient histograms: int8 x int8 MXU contractions with int32
    accumulation (2x bf16 throughput on v5e+).
    """
    F, n = binned_t.shape
    W = int(num_nodes)
    B = int(num_bins)
    eng = _select_engine(n, F, 3 * W, B, fused_w=W, quantized=quantized)
    if eng == "pallas":
        out = _node_hist_pallas(binned_t, row_pos, base_t, W, B,
                                quantized=quantized)
    elif eng == "scatter":
        # the position rides inside the scatter segment id, so neither the
        # [3W, n] masked stats nor any [n, B] one-hot ever materializes.
        # Non-quantized stats round to bf16 first — the same input rounding
        # the one-hot engines apply — and accumulate in f32; int8 stats
        # accumulate exactly in int32 (the scatter mirror of the MXU path).
        if quantized:
            out = node_hist_scatter(binned_t, row_pos, base_t, W, B,
                                    acc_dtype=jnp.int32)
        else:
            out = node_hist_scatter(binned_t, row_pos, round_stats(base_t),
                                    W, B)
    else:
        woh = row_pos[None, :] == jnp.arange(W, dtype=row_pos.dtype)[:, None]
        if quantized:
            # exact int32 accumulation (the XLA mirror of the int8 MXU
            # path); operands stay int8 so the masked-stats and one-hot
            # transients cost half the bf16 path, not 2x
            sb = jnp.where(woh[:, None, :], base_t[None, :, :],
                           jnp.int8(0)).reshape(3 * W, n)
            out = _hist_xla(binned_t, sb, B, acc_dtype=jnp.int32)
        else:
            sb = jnp.where(woh[:, None, :], base_t[None, :, :], 0.0)
            return _hist_xla(binned_t,
                             sb.reshape(3 * W, n).astype(jnp.bfloat16), B)
    return out


# ---------------------------------------------------------------------------
# XLA fallback formulations (CPU tests / shapes the kernel can't tile)
# ---------------------------------------------------------------------------


def _hist_xla(binned_t, stats_t, B, acc_dtype=jnp.float32):
    F, n = binned_t.shape
    # feature chunk size bounded by the one-hot budget for a full row pass
    fc = max(1, min(F, _ONEHOT_BUDGET // max(n * B, 1)))
    if n * B <= _ONEHOT_BUDGET:
        return _hist_feature_scan(binned_t, stats_t, B, fc, acc_dtype)
    # rows too large for even one feature at a time: block rows too
    rows_per_block = max(1, _ONEHOT_BUDGET // B)
    rows_per_block = max(8, (rows_per_block // 1024) * 1024 or rows_per_block)
    return _hist_row_blocks(binned_t, stats_t, B, rows_per_block, acc_dtype)


def _hist_feature_scan(binned_t, stats_t, B, fc, acc_dtype=jnp.float32):
    F, n = binned_t.shape
    S = stats_t.shape[0]
    n_chunks = -(-F // fc)
    Fp = n_chunks * fc
    if Fp != F:
        binned_t = jnp.pad(binned_t, ((0, Fp - F), (0, 0)), constant_values=0)
    chunks = binned_t.reshape(n_chunks, fc, n)
    bins = jnp.arange(B, dtype=binned_t.dtype)

    def body(_, chunk):  # chunk [fc, n]
        oh = (chunk[:, :, None] == bins).astype(stats_t.dtype)  # [fc, n, B]
        h = jnp.einsum("sn,fnb->fsb", stats_t, oh,
                       preferred_element_type=acc_dtype)
        return _, h

    _, hists = lax.scan(body, None, chunks)  # [n_chunks, fc, S, B]
    return hists.reshape(Fp, S, B)[:F].astype(acc_dtype)


def _hist_row_blocks(binned_t, stats_t, B, rows_per_block,
                     acc_dtype=jnp.float32):
    F, n = binned_t.shape
    S = stats_t.shape[0]
    nb = -(-n // rows_per_block)
    n_pad = nb * rows_per_block
    if n_pad != n:
        binned_t = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)),
                           constant_values=0)
        stats_t = jnp.pad(stats_t, ((0, 0), (0, n_pad - n)))  # zero: no effect
    binned_b = binned_t.reshape(F, nb, rows_per_block)
    stats_b = stats_t.reshape(S, nb, rows_per_block)
    bins = jnp.arange(B, dtype=binned_t.dtype)

    def body(acc, xs):
        bb, sb = xs  # [F, R], [S, R]

        def feat_body(_, fchunk):  # fchunk [1, R]
            oh = (fchunk[:, :, None] == bins).astype(sb.dtype)  # [1, R, B]
            return _, jnp.einsum("sn,fnb->fsb", sb, oh,
                                 preferred_element_type=acc_dtype)

        _, h = lax.scan(feat_body, None, bb[:, None, :])
        return acc + h.reshape(F, S, B), None

    acc0 = jnp.zeros((F, S, B), dtype=acc_dtype)
    acc, _ = lax.scan(body, acc0,
                      (jnp.transpose(binned_b, (1, 0, 2)),
                       jnp.transpose(stats_b, (1, 0, 2))))
    return acc


# ---------------------------------------------------------------------------
# Pallas TPU kernels: the hot op of GBDT training.
#
# The XLA formulations above materialize the [n, B] one-hot (and the masked
# stats) in HBM, so at 1M rows x 255 bins they run bandwidth-bound at ~55 ms.
# The kernels below keep the one-hot entirely in VMEM: grid (n/RB,), each
# step builds a transposed [B, RB] one-hot in registers/VMEM per feature
# (bins on sublanes, rows on lanes — no relayout of the lane-major bin row),
# feeds the MXU with a lane-axis [S, RB] x [B, RB] contraction (folded
# layout: [2S, RB] x [128, RB], see _fold_words), and accumulates the
# [S, B] block in
# the output block that stays resident across the row-block axis (classic
# matmul accumulation pattern). Measured ~1.5 ms for the same shape — ~35x.
# ---------------------------------------------------------------------------

# v5e has 128 MiB of VMEM (pltpu.get_tpu_info().vmem_capacity_bytes); the
# compiler's default scoped-vmem limit is only 16 MB, which forces tiny
# row blocks (RB<=2048) once the unrolled feature loop keeps ~8 one-hot
# temporaries live — and the resulting 500-1000-step grids pay per-step
# overhead. Both pallas_calls therefore request a raised limit and the
# block picker budgets against it (with headroom: the compiler's
# accounting adds dot outputs, copies and padding beyond the blocks
# modeled below — a 12 MB budget was observed to produce a 16.15 MB scoped
# allocation at S=96).
_PALLAS_VMEM_LIMIT = 100 * 1024 * 1024
_PALLAS_VMEM_BUDGET = 64 * 1024 * 1024
# v2/v3 cores have only 16 MiB of physical VMEM — the raised limit would fail
# Mosaic compilation outright there, so those generations keep the old
# conservative budget and the compiler's default scoped limit.
_SMALL_VMEM_BUDGET = 10 * 1024 * 1024


def _small_vmem_device() -> bool:
    kind = jax.devices()[0].device_kind.lower()
    return ("v2" in kind) or ("v3" in kind)


def _vmem_budget() -> int:
    if _interpret_mode():
        return _PALLAS_VMEM_BUDGET  # interpreter: no physical limit
    return _SMALL_VMEM_BUDGET if _small_vmem_device() else _PALLAS_VMEM_BUDGET


def _compiler_params():
    """The raised scoped-vmem limit for both pallas_calls (None under the
    interpreter and on small-VMEM generations: the compiler default)."""
    if _interpret_mode() or _small_vmem_device():
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_PALLAS_VMEM_LIMIT)


def _bin_packing(B: int):
    """(BP, P): per-feature lane width and features packed per 128-lane dot.

    Small-bin configs (LightGBM's own GPU guidance recommends max_bin=63 on
    accelerators) would otherwise pad to 128 lanes and waste the MXU: with
    B <= 64 the kernel packs P = 128//BP features' one-hots side by side in
    one dot, cutting the unit-matmul count by P.
    """
    if B <= 64:
        BP = 1 << max(int(B - 1).bit_length(), 3)   # pow2, >= 8
        return BP, 128 // BP
    return -(-B // 128) * 128, 1


def _fold_words(B: int, S: int, itemsize: int) -> int:
    """The layout rule, from static shapes alone: 0 keeps the plain
    contraction ``[S, RB] x [BP, RB]``; ``k > 0`` picks the FOLDED one,
    where ``k`` 32-bit words hold the ``S`` stats rows (4 int8 or 2 bf16
    rows a word).

    With 128 < B <= 256 the plain layout pushes two 128-lane one-hot tiles
    a feature through the MXU, and on v5e building a tile costs the VPU more
    than the MXU takes to use it. A bin is ``hi * 128 + lo``: the folded
    layout builds ONE one-hot tile, of ``lo``, and moves ``hi`` to the stats
    side — the stats rows stacked twice, each copy kept where ``hi`` is its
    own — so ``sum_r A[(hi, s), r] * oh[lo, r] = hist[s, hi * 128 + lo]``,
    cell for cell the plain histogram. It needs both copies inside one
    128-row operand: ``S <= 64``, i.e. every leafwise pass up to
    ``leaf_batch = 10`` (W <= 21) and every root. Packed features
    (B <= 64), one-tile bins (B <= 128) and wider stats keep the plain
    layout.
    """
    BP, P = _bin_packing(B)
    if P != 1 or BP != 256:
        return 0
    k = -(-max(S, 1) // (4 // itemsize))
    return k if _fold_rows(k, itemsize) <= 128 else 0


def _fold_rows(k: int, itemsize: int) -> int:
    """Rows of the folded stats operand (and of its accumulator block): two
    copies of ``k`` words, padded to whole 8-sublane word tiles."""
    return -(-2 * k // 8) * 8 * (4 // itemsize)


def _pick_row_block(n: int, F: int, S: int, B: int, fused_w: int = 0,
                    quantized: bool = False) -> int:
    """Largest row-block size whose resident VMEM fits the budget.

    VMEM model (matches the kernels): input blocks are double-buffered across
    grid steps (binned [Fp, RB] int32 and stats [Sp, RB] bf16 — or, fused,
    [8, RB] f32 base + [1, RB] i32 positions); the [Fp, Sp, BP] f32
    accumulator stays resident; kernel scratch is the packed transposed one-hot
    [max(BP,128), RB] bf16 plus, fused, the rebuilt [W, 3, RB] + [Sp, RB]
    masked stats. int8 (quantized) scratch is charged at 4 B/elem, not 1:
    Mosaic widens narrow-sublane int8 tiles internally, and the measured
    stack footprint tracks the 32-bit accounting (a 1 B model produced a
    16.8 MB scoped allocation against the 16 MB limit at W=31, B=63).

    When the feature loop is statically unrolled (groups <= the unroll cap),
    Mosaic software-pipelines the unrolled iterations and keeps ~8 one-hot
    temporaries live on the kernel stack at once — measured on v5e: 38.0 MB
    scoped at RB=8192 and 19.2 MB at RB=4096 for B=255/W<=16, i.e. ~8x the
    single-buffer model. Charge 8 one-hot buffers in that case so the chosen
    RB actually compiles on hardware.

    The folded layout (:func:`_fold_words`) is modeled from what this
    compiler allocates for it (libtpu 0.0.34, the smallest scoped limit
    that compiles, F=39 uint8 bins): 2.25 MB at W=16 int8 RB=4096, 4.75 MB
    at RB=8192, 6.0 MB at W=21, 3.25 MB at W=16 bf16 RB=8192, 1.0 MB at
    W=1 — the input blocks and the rebuilt masked stats (the 36 B a row
    and node that the fused terms below already charge for int8) and no
    one-hot at all: the unrolled features' tiles live and die in vregs.
    So it charges the stats stacked twice as 32-bit words and, for spills,
    one one-hot tile and one folded operand, not eight.

    A plain kernel of one tile a feature group (B <= 128) never needed the
    eight buffers either, with the int32 compare or with the packed words
    that int8 statistics get since (:func:`_onehot_packed`): same compiler
    and table, RB=8192, the smallest limit that compiles is 4.75 MB at 63
    bins W=16, 1.25 MB at W=1 and 5.75 MB at 128 bins W=16 for both
    builds; packed, 8.5 MB at 63 bins W=31 and 4.75 MB at 31 bins W=16.
    The eight were the two-tile 255-bin kernel's. The charge stays as
    headroom: it takes 8192 rows at every shape of the cells already, and
    dropping it would only double the block, and with it the unrolled
    program the compiler has to schedule, for wide tables at frontiers
    past 40 nodes, with no pass measured there.
    """
    BP, P = _bin_packing(B)
    Fp = -(-F // P) * P
    Sp = -(-max(S, 1) // 16) * 16
    elt = 4 if quantized else 2
    onehot_bufs = 8 if (Fp // P) <= _unroll_max() else 1
    live = onehot_bufs * max(BP, 128) * elt         # bytes a row of RB
    out_rows = Sp
    itemsize = 1 if quantized else 2
    fold_k = _fold_words(B, S, itemsize)
    if fold_k:
        out_rows, BP = _fold_rows(fold_k, itemsize), 128
        live = (128 + out_rows) * elt + out_rows * itemsize
    for RB in (8192, 4096, 2048, 1024, 512):
        if RB > max(512, n):
            continue  # don't pad a small input up to a huge block
        binned_block = Fp * RB * 4
        if fused_w:
            in_blocks = binned_block + RB * 4 + 8 * RB * 4
            scratch = (RB * live
                       + 2 * (fused_w * 3 * RB * elt) + Sp * RB * elt)
        else:
            in_blocks = binned_block + Sp * RB * 2
            scratch = RB * live
        out_block = Fp * out_rows * BP * 4
        if 2 * in_blocks + out_block + scratch <= _vmem_budget():
            return RB
    return 0


def _hist_dot_accumulate(o_ref, b_ref, sb, Fp: int, BP: int, P: int,
                         fold_k: int = 0):
    """Shared inner loop: per step, pack P features' one-hots into one
    128-lane dot with the [Sp, RB] stats and accumulate the [Sp, BP] slices
    into their o_ref rows (``fold_k``: the folded layout's one tile a
    feature, see :func:`_fold_words`). int8 stats accumulate in int32 (the
    2x-rate MXU path); bf16 in f32.

    The feature loop is a static Python unroll, NOT lax.fori_loop: the
    dynamically-indexed loop measured ~3-5 us of scalar-core overhead per
    step (that loop's time was flat in B and W — it ran no faster at B=63
    than B=255), dominating the whole pass at ~17 ms for F=28 x 1M rows.
    Unrolled, Mosaic schedules the slices statically, and a pass costs its
    tiles: one-hot tiles a feature, operand tiles of stats rows (PERF.md
    §5: at 255 bins int8 0.114 s at W = 1, 0.127 at 8, 0.191 at 16).
    Above _UNROLL_MAX feature groups the loop stays dynamic so very wide
    datasets don't pay linear-in-F compile time/program size for a
    sub-us-per-step win.
    """
    acc = jnp.int32 if sb.dtype == jnp.int8 else jnp.float32
    if fold_k:
        words = _stack_stats_words(sb, fold_k, o_ref.shape[1])
        dtype = sb.dtype

        def group_dot(g):
            _hist_fold_dot(o_ref, b_ref, words, g, fold_k, dtype, acc)
    else:
        def group_dot(g):
            _hist_group_dot(o_ref, b_ref, sb, g, BP, P, acc)

    groups = Fp // P
    if groups > _unroll_max():
        def body(g, _):
            group_dot(g)
            return 0

        lax.fori_loop(0, groups, body, 0)
        return
    for g in range(groups):
        group_dot(g)


_UNROLL_MAX = 128


def _unroll_max() -> int:
    """Unroll cap, overridable via MMLSPARK_TPU_HIST_UNROLL_MAX (0 keeps the
    dynamic fori_loop everywhere — the escape hatch if a Mosaic version
    compiles large unrolled kernels pathologically)."""
    v = os.environ.get("MMLSPARK_TPU_HIST_UNROLL_MAX", "").strip()
    if not v:
        return _UNROLL_MAX
    try:
        return int(v)
    except ValueError:
        raise ValueError(
            f"MMLSPARK_TPU_HIST_UNROLL_MAX must be an integer, got {v!r}"
        ) from None


def _hist_group_dot(o_ref, b_ref, sb, g, BP: int, P: int, acc):
    """One feature group: build P features' one-hots, dot, accumulate.

    The one-hot is built TRANSPOSED — bins on sublanes, rows staying on
    lanes — and contracted on the lane axis of both operands. The naive
    orientation (``row[:, None] == iota[RB, BP]``) forces a lane->sublane
    relayout of the [RB] bin row for every feature in every grid step;
    measured on v5e that relayout dominated the whole kernel — 2.4x slower
    per pass at 1M rows x 28 features x 255 bins, with that pass's time
    flat in both bin count and stats dtype (the signature of a non-MXU
    bottleneck; transposed, neither is flat any more, nor is the node axis).
    """
    # widen narrow bin storage (uint8/int16) per block, in VMEM only
    rows = [b_ref[g * P + p, :].astype(jnp.int32) for p in range(P)]
    h = lax.dot_general(sb, _onehot_tile(rows, BP, sb.dtype),
                        (((1,), (1,)), ((), ())), preferred_element_type=acc)
    if P == 1:
        o_ref[g] += h
    else:
        for p in range(P):
            o_ref[g * P + p] += h[:, p * BP:(p + 1) * BP]


def _stack_stats_words(sb, k: int, rows: int):
    """Once a row block: the [k * pack, RB] stats as ``k`` packed 32-bit
    words a row, stacked twice (words 0..k-1 for ``hi == 0``, k..2k-1 for
    ``hi == 1``) and zero-padded to ``rows // pack`` words. Everything the
    fold does per feature then runs on words: a quarter (int8) or half
    (bf16) of the vregs that the same select costs on the rows."""
    w = pltpu.bitcast(sb, jnp.int32)                # [k, RB]
    parts = [w, w]
    pad = rows * sb.dtype.itemsize // 4 - 2 * k
    if pad:
        parts.append(jnp.zeros((pad, w.shape[1]), jnp.int32))
    return jnp.concatenate(parts, axis=0)


def _onehot_packed(dtype, BP: int, P: int) -> bool:
    """The tile-builder rule, from static shapes alone: True builds the
    one-hot tile of ``P`` features at ``BP`` sublanes each on packed 32-bit
    words (:func:`_onehot_tile`), False compares in int32.

    Packed wants int8 statistics (bf16 costs 5 VALU operations a vreg
    either way and compares) and every value of the tile under 128, i.e.
    exactly one 128-sublane tile, ``BP * P == 128``: packed features at 8
    to 64 bins, one feature at 65 to 128, and the folded layout's
    ``bin & 127``. The plain layout at ``BP >= 256`` (W > 21 at 255 bins,
    int16/int32 bins past 256) compares.
    """
    return jnp.dtype(dtype) == jnp.int8 and BP * P == 128


def _onehot_tile(rows, BP: int, dtype):
    """Transposed one-hot ``[P * BP, RB]`` of ``P = len(rows)`` bin rows:
    sublane ``p * BP + b`` is hot where ``rows[p] == b``. THE tile builder
    of both layouts (the folded one's call is ``P = 1`` on ``bin & 127``).

    Where :func:`_onehot_packed`, it is built on packed words, four
    sublanes a word: with a bin and a sublane's own bin index both under
    128, a byte of ``0x80808080 - (row4 ^ index4)`` keeps its top bit
    exactly where they agree and no borrow crosses a byte, so a tile costs
    4 VALU operations a vreg (xor, sub, shift, and) where the int32
    compare, select and two packs cost 13. Word ``w`` of the 32 compares
    the feature that owns it, ``w // (BP // 4)``, against the indices
    ``4 (w % (BP // 4)) + byte``: whole 8-sublane word tiles up to
    ``P = 4``, a select between the owners inside a word tile beyond. The
    words' sublanes are read back off :func:`pltpu.bitcast`'s own layout
    (word i, byte b = sublane 4 i + b).

    Contract: ``0 <= rows[p] < 128``, which ``B <= BP <= 128`` gives.
    Packed or compared, a bin in ``[B, BP)`` lands on its own feature's
    padding sublanes (:func:`_to_hist` slices them off) and one in
    ``[BP, 128)`` matches nothing; past 127 only the compare still matches
    nothing, the packed bytes would borrow from their neighbours. Padding
    features (:func:`_pad_features_to`) bin to 0 and padding rows carry
    zero statistics.
    """
    P, RB = len(rows), rows[0].shape[0]
    if not _onehot_packed(dtype, BP, P):
        bins = lax.broadcasted_iota(jnp.int32, (BP, RB), 0)
        pieces = [(row[None, :] == bins).astype(dtype) for row in rows]
        return pieces[0] if P == 1 else jnp.concatenate(pieces, axis=0)
    rows4 = [row * 0x01010101 for row in rows]
    wpf = BP // 4                                   # words a feature
    if wpf >= 8:
        tiles = [jnp.broadcast_to(r[None, :], (wpf, RB)) for r in rows4]
    else:
        # several owners inside one 8-sublane word tile: select between them
        word = lax.broadcasted_iota(jnp.int32, (8, RB), 0)
        tiles = []
        for t in range(0, P, 8 // wpf):
            owners = rows4[t:t + 8 // wpf]
            tile = jnp.broadcast_to(owners[-1][None, :], (8, RB))
            for i in range(len(owners) - 2, -1, -1):
                tile = jnp.where(word < (i + 1) * wpf, owners[i][None, :],
                                 tile)
            tiles.append(tile)
    row4 = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)
    word = lax.broadcasted_iota(jnp.int32, (32, RB), 0)
    if P > 1:
        word &= wpf - 1                             # index inside its owner
    index4 = word * 0x04040404 + 0x03020100
    hit = jnp.int32(-0x7F7F7F80) - (row4 ^ index4)      # 0x80808080 - x
    return pltpu.bitcast(lax.shift_right_logical(hit, 7) & 0x01010101,
                         jnp.int8)


def _hist_fold_dot(o_ref, b_ref, words, g, k: int, dtype, acc):
    """One feature of the folded layout: one 128-bin one-hot tile, the
    bin's high bit selecting which stacked copy of the stats is live."""
    row = b_ref[g, :].astype(jnp.int32)             # [RB], rows on lanes
    copy = (lax.broadcasted_iota(jnp.int32, words.shape, 0) >= k
            ).astype(jnp.int32)
    a = jnp.where(copy == (row >> 7)[None, :], words, 0)
    h = lax.dot_general(pltpu.bitcast(a, dtype),
                        _onehot_tile([row & 127], 128, dtype),
                        (((1,), (1,)), ((), ())), preferred_element_type=acc)
    o_ref[g] += h


def accumulator_tile(B: int, S: int, itemsize: int):
    """(rows, lanes) of one feature's accumulator block in a kernel of ``S``
    stats rows: the stats operand's height (the folded layout's stacked
    words, or the stats padded to a 16-sublane tile) by the one-hot's
    sublanes. The height is what the MXU pays for once it passes the first
    tile, and the pair is how two widths of a pass are known to be the same
    kernel (``growth._pass_widths``)."""
    fold_k = _fold_words(B, S, itemsize)
    if fold_k:
        return _fold_rows(fold_k, itemsize), 128
    return -(-S // 16) * 16, _bin_packing(B)[0]    # pad stats to sublane tile


def _stage_layout(B: int, S: int, Fp: int, dtype):
    """(fold_k, stats rows Sp, accumulator block) of a kernel being staged
    out, counted in hist_kernel_layout_total and, by the build its one-hot
    tiles get, in hist_kernel_onehot_total."""
    itemsize = jnp.dtype(dtype).itemsize
    fold_k = _fold_words(B, S, itemsize)
    BP, P = (128, 1) if fold_k else _bin_packing(B)
    _count_build("hist_kernel_layout_total",
                 layout="folded" if fold_k else "plain")
    _count_build("hist_kernel_onehot_total",
                 onehot="packed" if _onehot_packed(dtype, BP, P)
                 else "compare")
    rows, lanes = accumulator_tile(B, S, itemsize)
    return (fold_k, fold_k * 4 // itemsize if fold_k else rows,
            (Fp, rows, lanes))


def _to_hist(out, F: int, S: int, B: int, fold_k: int, Sp: int):
    """The accumulator block as ``[F, S, B]``; a folded one's two copies of
    the stats rows, put side by side, are the bins' two halves."""
    if not fold_k:
        return out[:F, :S, :B]
    return jnp.concatenate([out[:F, :S], out[:F, Sp:Sp + S]], axis=2)[:, :, :B]


def _make_hist_kernel(Fp: int, BP: int, P: int, fold_k: int = 0):
    def kernel(b_ref, s_ref, o_ref):
        j = pl.program_id(0)
        sb = s_ref[:, :]                            # [Sp, RB] bf16

        @pl.when(j == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        _hist_dot_accumulate(o_ref, b_ref, sb, Fp, BP, P, fold_k)

    return kernel


def _make_node_hist_kernel(Fp: int, W: int, Sp: int, BP: int, P: int,
                           quantized: bool = False, fold_k: int = 0):
    def kernel(b_ref, p_ref, base_ref, o_ref):
        j = pl.program_id(0)
        pos = p_ref[0, :]                           # [RB] int32
        if quantized:
            base = base_ref[0:3, :]                 # [3, RB] int8
            zero = jnp.int8(0)
        else:
            base = base_ref[0:3, :].astype(jnp.bfloat16)  # [3, RB]
            zero = jnp.bfloat16(0.0)
        woh = (lax.broadcasted_iota(jnp.int32, (W, pos.shape[0]), 0)
               == pos[None, :])                     # [W, RB] bool
        sb = jnp.where(woh[:, None, :], base[None, :, :],
                       zero).reshape(3 * W, pos.shape[0])
        if Sp != 3 * W:
            sb = jnp.pad(sb, ((0, Sp - 3 * W), (0, 0)))

        @pl.when(j == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        _hist_dot_accumulate(o_ref, b_ref, sb, Fp, BP, P, fold_k)

    return kernel


def _pad_rows_to(x, n_pad, fill=0):
    n = x.shape[-1]
    if n_pad == n:
        return x
    width = [(0, 0)] * (x.ndim - 1) + [(0, n_pad - n)]
    return jnp.pad(x, width, constant_values=fill)


def _pad_features_to(binned_t, Fp):
    F = binned_t.shape[0]
    if Fp == F:
        return binned_t
    # padding features bin everything to 0; their histogram rows are sliced
    # off the output
    return jnp.pad(binned_t, ((0, Fp - F), (0, 0)), constant_values=0)


def _hist_pallas(binned_t: jnp.ndarray, stats_t: jnp.ndarray,
                 num_bins: int) -> jnp.ndarray:
    F, n = binned_t.shape
    S = stats_t.shape[0]
    B = int(num_bins)
    BP, P = _bin_packing(B)
    Fp = -(-F // P) * P
    fold_k, Sp, out_block = _stage_layout(B, S, Fp, stats_t.dtype)
    RB = _pick_row_block(n, F, S, B)
    n_pad = -(-max(n, RB) // RB) * RB
    # zero stats on padding rows: they contribute nothing to any bin
    binned_t = _pad_features_to(_pad_rows_to(binned_t, n_pad), Fp)
    stats_t = _pad_rows_to(stats_t, n_pad)
    if Sp != S:
        stats_t = jnp.pad(stats_t, ((0, Sp - S), (0, 0)))
    nb = n_pad // RB

    out = pl.pallas_call(
        _make_hist_kernel(Fp, BP, P, fold_k),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((Fp, RB), lambda j: (0, j)),
            pl.BlockSpec((Sp, RB), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec(out_block, lambda j: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_block, jnp.float32),
        interpret=_interpret_mode(),
        compiler_params=_compiler_params(),
        name="gbdt_hist_kernel",
    )(binned_t, stats_t)
    return _to_hist(out, F, S, B, fold_k, Sp)


def _node_hist_pallas(binned_t: jnp.ndarray, row_pos: jnp.ndarray,
                      base_t: jnp.ndarray, W: int, B: int,
                      quantized: bool = False) -> jnp.ndarray:
    F, n = binned_t.shape
    S = 3 * W
    BP, P = _bin_packing(B)
    Fp = -(-F // P) * P
    fold_k, Sp, out_block = _stage_layout(
        B, S, Fp, jnp.int8 if quantized else jnp.bfloat16)
    RB = _pick_row_block(n, F, S, B, fused_w=W, quantized=quantized)
    n_pad = -(-max(n, RB) // RB) * RB
    binned_t = _pad_features_to(_pad_rows_to(binned_t, n_pad), Fp)
    # padding rows: position -1 matches no frontier node -> contribute nothing
    row_pos = _pad_rows_to(row_pos, n_pad, fill=-1)[None, :]
    # base rides [8, n] sublane-aligned (f32; int8 when quantized — Mosaic
    # relayouts the narrower sublane tile); rows 3..7 are dead padding
    base8 = jnp.pad(base_t, ((0, 5), (0, 0)))
    base8 = _pad_rows_to(base8, n_pad)
    nb = n_pad // RB
    out_dtype = jnp.int32 if quantized else jnp.float32

    out = pl.pallas_call(
        _make_node_hist_kernel(Fp, W, Sp, BP, P, quantized, fold_k),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((Fp, RB), lambda j: (0, j)),
            pl.BlockSpec((1, RB), lambda j: (0, j)),
            pl.BlockSpec((8, RB), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec(out_block, lambda j: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_block, out_dtype),
        interpret=_interpret_mode(),
        compiler_params=_compiler_params(),
        name="gbdt_node_hist_kernel",
    )(binned_t, row_pos, base8)
    return _to_hist(out, F, S, B, fold_k, Sp)
