"""Leaf-wise (best-first) tree growth as a fixed-shape XLA program.

TPU-native replacement for LightGBM's C++ tree learner invoked per iteration
through LGBM_BoosterUpdateOneIter (reference: lightgbm/TrainUtils.scala:246,
with distributed semantics of the ``data_parallel`` learner —
lightgbm/LightGBMParams.scala:13-18). Where the reference mutates dynamic row
sets per leaf, the TPU formulation keeps everything static-shape:

  * a tree is ``M = 2*num_leaves - 1`` preallocated node slots;
  * each row carries its current node id (``row_node``), updated by masked
    ``where`` — no repartitioning;
  * each of the ``num_leaves - 1`` split rounds is one ``fori_loop`` step:
    pick the cached best leaves, build their children's histograms in a
    single MXU pass (float statistics: grad/hess/count of both children;
    int8: of the left child, the right one being its parent's int32 sums
    less the left's), find their best splits, record the split — all
    data-dependent choices via argmax + where, never Python control flow.

Layout: the binned matrix rides **column-major** (``binned_t``: [F, n]) for
the whole training run — histogram row blocks and per-feature column reads
are then contiguous device slices, with no per-level transposes or per-row
feature gathers (both measured dominators of the row-major formulation).

Run inside ``shard_map`` with rows sharded over the ``data`` axis, the single
``psum`` on histograms reproduces the reference's per-iteration histogram
all-reduce over its TCP ring (TrainUtils.scala:496-512), but on ICI.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ...ops.histogram import (accumulator_tile, dequantize_node_histogram,
                              node_histogram, node_histogram_sums,
                              quant_q_max, quantize_stats, round_stats)
from ...parallel.compat import axis_size as _axis_size

NEG_INF = jnp.float32(-jnp.inf)


class GrowConfig(NamedTuple):
    num_leaves: int = 31
    max_depth: int = -1  # <0: unlimited (bounded by num_leaves chain)
    num_bins: int = 255
    learning_rate: float = 0.1
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    # "leafwise" = LightGBM-parity best-first growth. "depthwise" =
    # TPU-throughput mode: one histogram pass per LEVEL with every frontier
    # node's stats batched into the stat axis (a pass scans all rows
    # whatever the nodes hold and pays for that axis by the MXU operand
    # tile, see _pass_widths, so a 31-leaf tree takes ~6 passes, each as
    # wide as its level, instead of 30); the num_leaves budget is enforced
    # by splitting the best nodes first.
    growth_policy: str = "leafwise"
    # leafwise batching: split the top ``leaf_batch`` pending leaves (by
    # cached gain) per histogram pass instead of one. Splits of distinct
    # leaves are independent (disjoint row sets), so batching only changes
    # the ORDER splits are taken in — which matters solely when num_leaves
    # runs out mid-batch and a child's gain would have outranked a pending
    # leaf's. leaf_batch=1 is exact sequential best-first (LightGBM order);
    # the default trades that tail-order nuance for ~4-5x fewer passes.
    # A histogram pass scans all rows whatever the nodes hold, so batching
    # cuts the PASS COUNT. Its cost is not flat in the node axis: flat up
    # to 4 nodes, then paid for by the MXU (a 16-node pass costs 1.7x a
    # root's at 255 bins), so a round's pass runs at the narrowest staged
    # width that holds its live children (_pass_widths).
    # Caveat under voting_parallel: the top-2k feature ballot then spans the
    # whole batch's children (one vote per pass, like depthwise's
    # frontier-wide vote) rather than one split's two children, so voting
    # runs are a batch-wide approximation, not a pure reordering — voting
    # is itself an approximate-split mode, and leaf_batch=1 restores the
    # per-split ballot exactly.
    leaf_batch: int = 8
    # voting_parallel (reference: lightgbm/LightGBMParams.scala:13-27,
    # LightGBMConstants.scala:24 DefaultTopK): shards vote on locally-best
    # top_k features; only the globally top 2k features' histograms are
    # all-reduced — two small collectives instead of one [F,3,B] psum.
    voting: bool = False
    top_k: int = 20
    # categorical splits (reference ingests categorical metadata natively:
    # core/schema/Categoricals.scala, LightGBMUtils.scala:227,256): category
    # bins are sorted by smoothed gradient ratio and scanned as prefixes
    # (LightGBM's sorted-subset search); the chosen subset is a bitset.
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    # quantized-gradient histograms (LightGBM use_quantized_grad): grad/hess
    # quantize to int8 per tree (stochastic rounding) and histograms ride
    # the 2x-rate int8 MXU path with exact int32 accumulation.
    quantized_grad: bool = False
    # Quantized-mode quality controls (both only engage with quantized_grad):
    # - quant_renew_leaf (LightGBM quant_train_renew_leaf): after growing a
    #   quantized tree, recompute the LEAF grad/hess/count sums from the
    #   original f32 stats with one segment-sum over the final row->leaf map,
    #   so leaf outputs carry no quantization error (split STRUCTURE still
    #   comes from int8 histograms — that's where the 2x MXU win lives).
    # - quant_warmup_iters: run the first k boosting iterations at full
    #   precision before switching to int8. Early iterations on targets with
    #   near-zero marginal gains (pure interactions) are where quantization
    #   noise can misroute split selection; after the ensemble has carved the
    #   first partitions, per-node gains are real and int8 selection matches.
    #   Runtime cost: warmup iterations run at bf16 histogram rate; both
    #   variants live in one compiled program (lax.cond), so fused scans and
    #   the early-stopping while_loop keep their single-dispatch shape.
    quant_renew_leaf: bool = True
    quant_warmup_iters: int = 2
    # LightGBM max_delta_step: clamp each leaf's raw output (pre-shrinkage)
    # to +-this; 0 disables. Stabilizes extreme leaf values (LightGBM
    # recommends it for poisson / highly imbalanced binary).
    max_delta_step: float = 0.0
    # Deterministic histogram-reduction geometry (topology-independent
    # training). 0 = the plain path: per-shard histograms psum'd across the
    # mesh — fast, but f32 accumulation order (and therefore the last ulp
    # of every gain and leaf value) depends on the device count. An int
    # k >= 2 pins a CANONICAL geometry instead: rows are processed as k
    # fixed blocks, per-block histograms/stat-sums are all_gather'd in
    # block order and folded left-to-right, and quantized-gradient scales/
    # rounding derive from global row indices — so every device count
    # dividing k grows BIT-IDENTICAL trees (model_string() equality at
    # k=8 across 1/2/4/8 devices; the preemption-resume story across
    # topology changes). Costs one gathered [k, F, 3W, B] transient per
    # pass. "auto" (default) resolves via placement.resolve_hist_blocks
    # (MMLSPARK_TPU_HIST_BLOCKS, default 0) BEFORE entering any
    # compiled-program cache key; unresolved "auto" reaching growth behaves
    # as 0.
    hist_blocks: "int | str" = "auto"


# ---------------------------------------------------------------------------
# Deterministic blocked reduction (GrowConfig.hist_blocks): the canonical
# geometry that makes sharded training topology-independent. Every reduction
# that crosses rows — histograms, stat totals, leaf renewal — is computed per
# fixed row block, gathered into canonical block order, and folded
# left-to-right, so the f32 rounding sequence is a function of the BLOCK
# COUNT, never of how many devices happen to hold the blocks.
# Scope: the contract covers the TRAINING reductions (histograms, stat
# totals, quantization, leaf renewal). Validation METRIC combining stays a
# psum — early stopping driven by a valid set may therefore stop at a
# different round across topologies when a round's metric lands within one
# ulp of the best; fits without validation-driven stopping are
# bit-identical end to end (docs/performance.md "Sharded training").
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Cross-shard reductions: one funnel
# ---------------------------------------------------------------------------


def _allreduce(x, axis_name, what: str, per: str = "tree", op=lax.psum,
               **kw):
    """``op(x, axis_name)`` for tree growth; the identity on one shard, where
    it emits nothing. Every collective of this module goes through here, under
    ``jax.named_scope("gbdt_allreduce")`` nested in the caller's scope, so a
    device trace tells the wire from the work.

    gbdt_allreduce_bytes_total{what, per}: the result's bytes on one shard,
    counted where the reduction is staged out (as ``_note_route``
    counts), so it tracks program builds and costs nothing on the device.
    ``per`` says how often the built program runs the site: once a ``tree``,
    once a leafwise ``round`` that runs, or once for the depthwise ``level``
    it belongs to (each level is a site of its own, and a level under the
    last split is skipped)."""
    if axis_name is None:
        return x
    with jax.named_scope("gbdt_allreduce"):
        out = op(x, axis_name, **kw)
    try:
        from ...observability import metrics as _metrics
        _metrics.safe_counter("gbdt_allreduce_bytes_total", what=what,
                              per=per).inc(out.size * out.dtype.itemsize)
    except Exception:  # noqa: BLE001 — telemetry must not fail the fit
        pass
    return out


def _allreduce_round_hist(h, axis_name):
    """``psum`` of a derived round's ``[F, 3 * W, B]`` f32 histograms, summed
    over the shards in the order the both-children program summed them in.

    Each shard's operand is that program's to the bit, but an f32 ``psum``
    is only as stable as its operand's layout: XLA adds the shards in an
    order that follows an element's place in memory (ROADMAP D14), and the
    compiler lays the operand out from what surrounds it. So the operand is
    handed over as the ``[W, F, 3, B]`` array split search reads, in the
    layout the both-children program's had on a v5e (stat-major: ``3, F, W,
    B`` in memory), and the trees of a sharded int8 fit stay that program's
    to the bit. Values do not depend on it, last bits do."""
    if axis_name is None:
        return h
    F, S, B = h.shape
    x = h.reshape(F, S // 3, 3, B).transpose(1, 0, 2, 3)
    x = with_layout_constraint(x, Layout(major_to_minor=(2, 1, 0, 3)))
    x = _allreduce(x, axis_name, "hist", "round")
    return x.transpose(1, 0, 2, 3).reshape(F, S, B)


def hist_blocks_per_shard(cfg: GrowConfig, shards: int) -> int:
    """Blocks of the blocked reduction on each of ``shards`` data shards: the
    kernel launches one histogram pass is there. 0 on the plain psum path,
    where a pass is one launch. Raises when a pinned block count cannot be
    dealt to the shards."""
    hb = cfg.hist_blocks
    if hb == "auto" or not hb or (isinstance(hb, int) and hb <= 1):
        return 0
    if cfg.voting:
        raise ValueError(
            "hist_blocks does not compose with voting_parallel (the "
            "shard-local ballot is inherently topology-dependent)")
    if hb % shards:
        raise ValueError(
            f"hist_blocks={hb} is not a multiple of the {shards}-shard "
            "data axis")
    return hb // shards


def _hist_block_geometry(cfg: GrowConfig, axis_name, n: int):
    """(blocks_local, rows_per_block) for the blocked reduction; (0, n) on
    the plain psum path. Raises when a pinned block count cannot tile this
    shard (train_booster resolves these cases up front via
    placement.resolve_hist_blocks; direct growth callers fail loudly)."""
    axis_sz = _axis_size(axis_name) if axis_name is not None else 1
    bl = hist_blocks_per_shard(cfg, axis_sz)
    if not bl:
        return 0, n
    if n % bl:
        raise ValueError(
            f"shard row count {n} does not tile into {bl} blocks "
            f"(hist_blocks={cfg.hist_blocks} over {axis_sz} shards)")
    return bl, n // bl


def _pass_widths(W: int, B: int, quantized: bool) -> tuple:
    """The node widths a leafwise round's histogram pass is staged at, from
    static shapes alone: the powers of two from 4 up to the round's ``W``
    node slots (``2 * leaf_batch`` children; ``leaf_batch`` left children
    where the siblings are derived, :func:`_sibling_is_derived`) and ``W``
    itself, less every width whose kernel would have the next one's
    accumulator tile (:func:`ops.histogram.accumulator_tile`: the wider of
    the two then costs the same MXU tiles and holds more). A round runs the
    narrowest that holds its live positions (:func:`_hist_at_width`).

    A pass scans all rows whatever the nodes hold, but its cost is not flat
    in the node axis: flat up to 4 nodes, then paid for by the MXU, a tile
    of stats rows at a time (68.3 M rows x 39 at 255 bins, measured on a
    v5e: int8 0.114 s at the root, 0.117 at 4 nodes, 0.126 at 8, 0.189 at
    16; bf16 0.225, 0.229, 0.235, 0.369, and 0.226 at 2 nodes: a variant
    under 4 would buy 3 ms a pass for one more kernel to compile and load;
    PERF.md §5). A 31-leaf tree at ``leaf_batch`` 8 splits 1, 2, 4, 8, 8, 7
    leaves in its rounds: the int8 pass's live positions, run at 4, 4, 4, 8,
    8, 8, and half the float pass's, run at 4, 4, 8, 16, 16, 16.
    ``leaf_batch`` 1 has the one width."""
    itemsize = 1 if quantized else 2
    widths = []
    for w in sorted({1 << i for i in range(2, W.bit_length())} | {W}):
        if widths and (accumulator_tile(B, 3 * widths[-1], itemsize)
                       == accumulator_tile(B, 3 * w, itemsize)):
            widths.pop()
        widths.append(w)
    return tuple(widths)


def _hist_at_width(hist_of, W: int, live, B: int, quantized: bool):
    """``(hist_of(W), which)`` — this shard's ``[..., 3 * W, B]`` node
    histograms, built at the narrowest staged width that holds the ``live``
    node positions (a traced count; positions at or past it hold no row) and
    zero-padded back to ``W``, so everything downstream sees the array it
    would of a full-width pass: the slots past ``live`` are zero either way;
    and the index into ``_pass_widths(W, B, quantized)`` of the width that
    ran. One ``lax.switch`` whose branches differ in the kernel call alone,
    on ``which``, so a count kept by it (the run tally,
    :func:`run_tally_layout`) cannot part from what ran; one width stages
    no switch."""
    def staged(w):
        def branch():
            h = hist_of(w)
            if w == W:
                return h
            pad = [(0, 0)] * h.ndim
            pad[-2] = (0, 3 * (W - w))
            return jnp.pad(h, pad)
        return branch

    widths = _pass_widths(W, B, quantized)
    if len(widths) == 1:
        return staged(W)(), 0
    narrower = jnp.asarray(widths[:-1], dtype=jnp.int32)
    which = jnp.sum((live > narrower).astype(jnp.int32))
    return lax.switch(which, [staged(w) for w in widths]), which


def _sibling_is_derived(quantized: bool) -> bool:
    """Whether a leafwise round histograms one child of every split and takes
    the other as its parent's histogram less that one
    (:func:`_derive_siblings`). It follows from the statistics' type alone.
    int8 statistics sum exactly in int32, so the difference is the sibling's
    own sum to the bit and the round's pass needs ``leaf_batch`` node slots
    where both children need twice that (a pass is paid for in node slots,
    :func:`_pass_widths`). A float difference at the parent's magnitude is
    the fault :func:`_stat_totals` describes: float statistics sum both
    children."""
    return quantized


def _level_widths(cfg: GrowConfig) -> tuple:
    """The node width of every level's pass of a depthwise tree, the root's
    first. Without an explicit max_depth, two levels of slack beyond the
    balanced depth let moderately skewed trees still spend the leaf budget
    (extreme skew is leafwise's domain — a perfectly unbalanced chain would
    need num_leaves-1 levels and defeat the batching)."""
    L = int(cfg.num_leaves)
    depth_cap = (cfg.max_depth if cfg.max_depth > 0
                 else min(L - 1, (L - 1).bit_length() + 2))
    return tuple(min(2 ** depth, L) for depth in range(depth_cap))


def run_tally_layout(cfg: GrowConfig) -> tuple:
    """The node widths a tree's run tally counts passes at, from the static
    configuration alone, for the host to label what the device counted.

    A grower carries the tally through its rounds, an int32 vector of ``1 +
    len(widths)`` scalars: ``tally[0]`` the sum over the tree's histogram
    passes of the node positions that held rows, ``tally[1 + i]`` how many
    passes ran at ``widths[i]`` node slots. ``widths[0]`` is the root's pass
    (one slot, one live position, run once, by construction); the others are
    a leafwise round's staged widths (:func:`_pass_widths`, the entry that
    :func:`_hist_at_width` ran) or a depthwise tree's levels under the root,
    one entry a level (:func:`_level_widths`). A round or level that is
    skipped adds nothing; under ``hist_blocks`` a pass is still one pass
    (:func:`hist_blocks_per_shard` launches). A round's live positions are
    its splits where the sibling is derived (:func:`_sibling_is_derived`),
    both children of every split where it is summed; a level's are its
    frontier's nodes. Every shard carries the same tally: what it counts by
    is reduced over the shards already."""
    if cfg.growth_policy == "depthwise":
        return _level_widths(cfg)
    KB = max(1, min(int(cfg.leaf_batch), int(cfg.num_leaves) - 1))
    derive = _sibling_is_derived(cfg.quantized_grad)
    return (1,) + _pass_widths(KB if derive else 2 * KB, int(cfg.num_bins),
                               cfg.quantized_grad)


@jax.named_scope("gbdt_hist")
def _derive_siblings(left, hsum, slots, do):
    """The int32 sums of both children of a round's ``KB`` candidates, from
    the pass over the left ones. ``left``: ``[..., F, 3 * KB, B]``, node
    position ``i`` the rows candidate ``i`` sends left; ``hsum``: ``[..., F,
    M, 3, B]``, every node's own sums by node slot; ``slots``: ``[KB]``, the
    candidates' slots; ``do``: ``[KB]``, which of them split. Returns
    ``[..., F, 2 * KB, 3, B]``, child ``2 i`` the left one and ``2 i + 1`` =
    parent - left the right one, zeros where ``do`` is false (as a pass over
    both children leaves the slots that hold no row)."""
    parents = jnp.take(hsum, slots, axis=-3)
    left = left.reshape(parents.shape)
    right = jnp.where(do[:, None, None], parents - left, 0)
    kids = jnp.stack([left, right], axis=-3)         # [..., F, KB, 2, 3, B]
    return kids.reshape(kids.shape[:-4] + (-1,) + kids.shape[-2:])


def _blocked_fold(parts: jnp.ndarray, axis_name, what: str, per="tree"):
    """Gather per-shard block partials into canonical order and fold them
    left-to-right. ``parts``: [blocks_local, ...] stacked partials; the
    explicit unrolled fold (not a reduce op) pins the f32 rounding order
    regardless of how XLA would lower an axis reduction."""
    parts = _allreduce(parts, axis_name, what, per, op=lax.all_gather,
                       axis=0, tiled=True)
    acc = parts[0]
    for j in range(1, parts.shape[0]):
        acc = acc + parts[j]
    return acc


def _positional_uniform(key, channels: int, n_local: int, axis_name):
    """[channels, n_local] uniforms derived from GLOBAL row indices.

    ``jax.random.uniform(key, shape)`` draws depend on position within the
    local shape, so a sharded run and a single-device run would round the
    same row differently. This hash (murmur3-style finalizers over the key
    words and the global row id) gives every global row the same draw on
    every topology — quality is ample for stochastic rounding."""
    kd = key
    try:
        kd = jax.random.key_data(key)
    except Exception:  # noqa: BLE001 — raw uint32 key arrays (default impl)
        pass
    kd = jnp.asarray(kd).astype(jnp.uint32).reshape(-1)
    k0, k1 = kd[0], kd[-1]
    idx = jnp.arange(n_local, dtype=jnp.uint32)
    if axis_name is not None:
        idx = idx + (jnp.uint32(n_local)
                     * lax.axis_index(axis_name).astype(jnp.uint32))
    ch = jnp.arange(channels, dtype=jnp.uint32)[:, None]
    x = (idx[None, :] ^ k0) + ch * jnp.uint32(0x9E3779B9)

    def _mix(v):
        v = (v ^ (v >> jnp.uint32(16))) * jnp.uint32(0x85EBCA6B)
        v = (v ^ (v >> jnp.uint32(13))) * jnp.uint32(0xC2B2AE35)
        return v ^ (v >> jnp.uint32(16))

    x = _mix(x ^ k1)
    x = _mix(x + jnp.uint32(0x27D4EB2F))
    return (x >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(
        1.0 / (1 << 24))


@jax.named_scope("gbdt_quantize")
def _quantize_for(cfg: GrowConfig, base_t, qkey, axis_name, blocks_local,
                  rows_per_block):
    """int8 stat quantization, topology-aware. Blocked mode derives the
    scales from the GLOBAL amax (pmax is exact, so every shard count
    computes the same scale), bounds the int32 accumulator by the
    rows-per-block (the actual per-accumulation row count), and draws the
    stochastic-rounding bits from global row indices."""
    if not blocks_local:
        return quantize_stats(base_t, qkey)
    amax = _allreduce(jnp.max(jnp.abs(base_t), axis=1), axis_name,
                      "quantize", op=lax.pmax)
    q_max = quant_q_max(rows_per_block)
    u = None if qkey is None else _positional_uniform(
        qkey, base_t.shape[0], base_t.shape[1], axis_name)
    return quantize_stats(base_t, qkey, amax=amax, q_max=q_max, u=u)


def _block_node_hists(binned_t, row_pos, base_t, W: int, B: int, qscales,
                      blocks_local: int, rows_per_block: int,
                      sums: bool = False):
    """[blocks_local, F, W*3, B]: this shard's part of the canonical blocked
    reduction, one engine pass per fixed row block (identical shapes on
    every topology), for :func:`_blocked_fold` to gather and fold in block
    order. ``sums``: each block's int32 sums of int8 statistics, not scaled
    yet (they widen to f32 before the fold, never after)."""
    def block(*segs):
        if sums:
            return node_histogram_sums(*segs, W, B, quantized=True)
        return node_histogram(*segs, W, B, scales=qscales)

    return jnp.stack([
        block(binned_t[:, j * rows_per_block:(j + 1) * rows_per_block],
              row_pos[j * rows_per_block:(j + 1) * rows_per_block],
              base_t[:, j * rows_per_block:(j + 1) * rows_per_block])
        for j in range(blocks_local)])


def _blocked_node_hist(binned_t, row_pos, base_t, W: int, B: int, qscales,
                       blocks_local: int, rows_per_block: int, axis_name,
                       per):
    """[F, W*3, B] histogram via the canonical blocked reduction."""
    return _blocked_fold(
        _block_node_hists(binned_t, row_pos, base_t, W, B, qscales,
                          blocks_local, rows_per_block),
        axis_name, "hist", per)


def _stat_totals(base_t, qscales, axis_name, blocks_local, rows_per_block):
    """[3] global grad/hess/count totals. Blocked mode folds per-block sums
    in canonical order; the plain path keeps the historical psum.

    The totals must be sums of the SAME values the histograms sum: every
    engine rounds float stats to bf16 on input, so they are rounded here
    too (:func:`round_stats` is that one rounding, in a form XLA does not
    elide). On the quantized path split search derives every right side as
    ``total - left``, exactly: the sums are int32 before the scale. On the
    float path nothing is derived from these totals any more: they are the
    root's recorded stats and no other node's. A candidate's right side is
    its own suffix sum, a child gets the pair its parent's winning candidate
    was scored with, and the gain's parent term is the histogram's own sum
    (:func:`_best_split`). Subtracting at the root's magnitude handed every
    rounding of a 3e6 f32 sum (spacing 0.25 at 68 M rows), the kernel's
    accumulation walk and the residue of this function's other summation
    order down the chain of right children into one small leaf, whose
    hessian then landed near zero and whose value exploded (PERF.md, PR 33).

    Quantized per-BLOCK sums accumulate in int32 (bounded: _quantize_for
    caps q_max by rows_per_block, so a block sum stays under 2^31) and
    widen to f32 BEFORE the cross-block fold — folding raw int32 across
    all hist_blocks would wrap once q_max * total_rows crosses 2^31
    (~17M rows at q_max=127). The f32 fold is the same rounding class as
    the plain path's scale-before-psum order, and stays deterministic:
    identical values folded in identical order on every topology."""
    if qscales is None:
        base_t = round_stats(base_t)
    if blocks_local:
        def block_sum(j):
            seg = base_t[:, j * rows_per_block:(j + 1) * rows_per_block]
            if qscales is not None:
                return jnp.sum(seg.astype(jnp.int32),
                               axis=1).astype(jnp.float32)
            return jnp.sum(seg, axis=1)

        tot = _blocked_fold(
            jnp.stack([block_sum(j) for j in range(blocks_local)]),
            axis_name, "totals")
        if qscales is not None:
            tot = tot * qscales
        return tot
    if qscales is not None:
        tot = jnp.sum(base_t.astype(jnp.int32), axis=1) * qscales
    else:
        tot = jnp.sum(base_t, axis=1)
    return _allreduce(tot, axis_name, "totals")


def _soft_threshold(g, l1):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


# The float path's sum sites and the form each takes: where a sum is taken at
# the node's own magnitude that was once a difference at its parent's (see
# :func:`_stat_totals`). The benchmark's float-gradient driver reads the
# counter below by these names.
FLOAT_SUM_SITES = {"right_side": "suffix_sum",
                   "child_totals": "candidate_pair",
                   "node_totals": "own_histogram"}


def _note_float_sums(site: str) -> None:
    """gbdt_float_sums_total{site, form}: one of ``FLOAT_SUM_SITES``, counted
    where it is staged out (as :func:`_note_route` counts), so it
    tracks program builds. The quantized path counts nothing: its int32 sums
    subtract exactly."""
    try:
        from ...observability import metrics as _metrics
        _metrics.safe_counter("gbdt_float_sums_total", site=site,
                              form=FLOAT_SUM_SITES[site]).inc()
    except Exception:  # noqa: BLE001 — telemetry must not fail the fit
        pass


def _suffix_sums(x):
    """``out[..., b] = sum(x[..., b + 1:])``: what a split after bin ``b``
    sends right, summed from the far end so that no term is larger than the
    result (float statistics; see :func:`_stat_totals`)."""
    s = lax.cumsum(x, axis=x.ndim - 1, reverse=True)
    return jnp.concatenate([s[..., 1:], jnp.zeros_like(s[..., :1])], axis=-1)


def _feature_best_gains(hist, fm, cfg):
    """[F] best LOCAL split gain per feature from a local [F, 3, B]
    histogram (node totals taken from the local histogram itself) — the
    per-shard vote of voting_parallel."""
    B = hist.shape[-1]
    gl = jnp.cumsum(hist[:, 0, :], axis=-1)
    hl = jnp.cumsum(hist[:, 1, :], axis=-1)
    cl = jnp.cumsum(hist[:, 2, :], axis=-1)
    tg, th, tc = gl[:, -1:], hl[:, -1:], cl[:, -1:]
    if cfg.quantized_grad:
        gr, hr, cr = tg - gl, th - hl, tc - cl
    else:
        _note_float_sums("right_side")
        gr, hr, cr = (_suffix_sums(hist[:, s, :]) for s in range(3))
    gain = (_leaf_objective(gl, hl, cfg) + _leaf_objective(gr, hr, cfg)
            - _leaf_objective(tg, th, cfg))
    ok = ((cl >= cfg.min_data_in_leaf) & (cr >= cfg.min_data_in_leaf)
          & (hl >= cfg.min_sum_hessian_in_leaf)
          & (hr >= cfg.min_sum_hessian_in_leaf) & fm[:, None])
    ok = ok.at[:, B - 1].set(False)
    return jnp.max(jnp.where(ok, gain, NEG_INF), axis=-1)


def _voting_select(h, feat_mask, cfg, axis_name, W, per):
    """voting_parallel feature selection (LightGBMParams.scala:13-27):
    each shard votes its top_k features by best local gain (max over the
    W frontier nodes), votes are psum'd, and only the global top-2k
    features' histograms are all-reduced — scattered back into a zeroed
    full array so downstream split search keeps static shapes.
    Returns (h_global, selected_mask)."""
    F, _, B = h.shape
    hw = h.reshape(F, W, 3, B).transpose(1, 0, 2, 3)          # [W, F, 3, B]
    g = jnp.max(jax.vmap(_feature_best_gains, in_axes=(0, None, None))(
        hw, feat_mask, cfg), axis=0)                           # [F]
    k = min(int(cfg.top_k), F)
    top_g, local_top = lax.top_k(g, k)
    # a shard with no locally-feasible split (all NEG_INF — common for
    # small nodes at deep levels) must not cast junk votes for the
    # arbitrary indices top_k returns
    ballots = (top_g > NEG_INF).astype(jnp.float32)
    votes = _allreduce(jnp.zeros(F).at[local_top].add(ballots), axis_name,
                       "votes", per)
    # deterministic tie-break toward low feature index on every shard
    _, sel = lax.top_k(votes - jnp.arange(F) * 1e-6, min(2 * k, F))
    sel = jnp.sort(sel)
    hsel = _allreduce(h[sel], axis_name, "hist", per)
    hfull = jnp.zeros_like(h).at[sel].set(hsel)
    return hfull, jnp.zeros(F, dtype=bool).at[sel].set(True)


def _leaf_objective(g, h, cfg):
    sg = _soft_threshold(g, cfg.lambda_l1)
    return sg * sg / (h + cfg.lambda_l2 + 1e-38)


def bitset_words(num_bins: int) -> int:
    return -(-int(num_bins) // 32)


def _pack_bits(member: jnp.ndarray) -> jnp.ndarray:
    """[B] bool -> [ceil(B/32)] uint32 bitset."""
    B = member.shape[0]
    BW = bitset_words(B)
    m = jnp.pad(member.astype(jnp.uint32), (0, BW * 32 - B))
    m = m.reshape(BW, 32)
    return jnp.sum(m << jnp.arange(32, dtype=jnp.uint32)[None, :], axis=1,
                   dtype=jnp.uint32)


def bit_test(bits: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """bits: [..., BW] uint32; idx: [...] int — membership test, broadcast
    over leading dims."""
    word = jnp.take_along_axis(
        bits, (idx >> 5)[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return ((word >> (idx.astype(jnp.uint32) & 31)) & 1).astype(bool)


@jax.named_scope("gbdt_split_find")
def _best_split(hist, tot_g, tot_h, tot_c, cfg: GrowConfig, feat_mask, allow,
                is_cat=None):
    """Best split of one node from its histogram — numeric or categorical.

    hist: [F, 3, B] (grad, hess, count per bin). Numeric features split
    "bin <= b" for b in [0, B-2]. Categorical features (``is_cat`` [F] bool)
    use LightGBM's sorted-subset search: bins ordered by smoothed ratio
    g/(h + cat_smooth), prefixes scanned as candidate left-subsets (capped at
    ``max_cat_threshold`` categories), the winner encoded as a bin bitset.
    Returns (gain, feat, bin, left_g, left_h, left_c, bits[BW] uint32,
    right) — ``bits`` is all-zero for a numeric winner.

    The two arithmetics, chosen statically by ``cfg.quantized_grad``.
    Quantized: a right side is ``tot - left`` (exact in int32 before the
    scale) and ``right`` is None: the caller takes ``parent - left``. Float:
    a right side is the suffix sum of the same bins in the same order, the
    gain's parent term is the feature's own full sum, and ``right`` is the
    winner's (right_g, right_h, right_c); ``tot_*`` are not read, so every
    number a small child gets is a sum of its own bins, good to f32's
    relative error at the child's magnitude whatever its parent holds.
    """
    B = hist.shape[-1]
    float_sums = not cfg.quantized_grad
    g, h, c = hist[:, 0, :], hist[:, 1, :], hist[:, 2, :]
    gl = jnp.cumsum(g, axis=-1)
    hl = jnp.cumsum(h, axis=-1)
    cl = jnp.cumsum(c, axis=-1)
    if float_sums:
        gr, hr, cr = _suffix_sums(g), _suffix_sums(h), _suffix_sums(c)
    prefix_ok = jnp.ones((hist.shape[0], B), dtype=bool)
    rank = None
    if is_cat is not None:
        # categorical tables: cumsums in smoothed-ratio order; empty bins
        # sort last (+inf) so prefixes enumerate real categories first
        ratio = jnp.where(c > 0, g / (h + cfg.cat_smooth), jnp.inf)
        order = jnp.argsort(ratio, axis=-1)                     # [F, B]
        rank = jnp.zeros_like(order).at[
            jnp.arange(order.shape[0])[:, None], order].set(
            jnp.broadcast_to(jnp.arange(B), order.shape))
        gs = jnp.take_along_axis(g, order, axis=-1)
        hs = jnp.take_along_axis(h, order, axis=-1)
        cs = jnp.take_along_axis(c, order, axis=-1)
        glc = jnp.cumsum(gs, axis=-1)
        hlc = jnp.cumsum(hs, axis=-1)
        clc = jnp.cumsum(cs, axis=-1)
        icat = is_cat[:, None]
        gl = jnp.where(icat, glc, gl)
        hl = jnp.where(icat, hlc, hl)
        cl = jnp.where(icat, clc, cl)
        if float_sums:
            gr = jnp.where(icat, _suffix_sums(gs), gr)
            hr = jnp.where(icat, _suffix_sums(hs), hr)
            cr = jnp.where(icat, _suffix_sums(cs), cr)
        # prefix length b+1 capped (LightGBM max_cat_threshold)
        prefix_ok = jnp.where(
            icat, jnp.arange(B)[None, :] < int(cfg.max_cat_threshold),
            prefix_ok)
    if float_sums:
        _note_float_sums("right_side")
        _note_float_sums("node_totals")
        tot_g, tot_h = gl[:, -1:], hl[:, -1:]
    else:
        gr, hr, cr = tot_g - gl, tot_h - hl, tot_c - cl
    gain = (_leaf_objective(gl, hl, cfg) + _leaf_objective(gr, hr, cfg)
            - _leaf_objective(tot_g, tot_h, cfg))
    ok = ((cl >= cfg.min_data_in_leaf) & (cr >= cfg.min_data_in_leaf)
          & (hl >= cfg.min_sum_hessian_in_leaf) & (hr >= cfg.min_sum_hessian_in_leaf)
          & feat_mask[:, None] & allow & prefix_ok)
    ok = ok.at[:, B - 1].set(False)  # last bin: empty right side
    gain = jnp.where(ok, gain, NEG_INF)
    flat = jnp.argmax(gain)
    f, b = flat // B, flat % B
    pick = lambda a: a[f, b]
    BW = bitset_words(B)
    if is_cat is None:
        bits = jnp.zeros(BW, dtype=jnp.uint32)
    else:
        member = is_cat[f] & (rank[f] <= b)                     # [B] bool
        bits = _pack_bits(member)
    right = (pick(gr), pick(hr), pick(cr)) if float_sums else None
    return (gain[f, b], f.astype(jnp.int32), b.astype(jnp.int32),
            pick(gl), pick(hl), pick(cl), bits, right)


# Widest bitset, in uint32 words, that row routing tests with a select chain
# (8192 bins); wider ones keep XLA's gather. On a v5e the gather costs about
# 7 ns an element whatever the width, the whole routing with the chain 0.05 ns
# at 2 and 8 words, 0.11 at 64 and 0.72 at 256 (PERF.md, PR 27), so by rate
# the chain would win up to some 2000 words. The bound is the unrolled chain's
# compile time, paid once a leafwise program and once a level depthwise: level
# with the gather's up to 256 words, five times it at 1024. uint8 bins give at
# most 8 words.
_ROUTE_SELECT_MAX_WORDS = 256


def _note_route(what: str, how: str) -> None:
    """gbdt_route_lookup_total{lookup} and gbdt_route_fetch_total{fetch}:
    counted where the routing is staged out (the choice follows from static
    shapes), so they track program builds and cost nothing on the device."""
    try:
        from ...observability import metrics as _metrics
        _metrics.safe_counter(f"gbdt_route_{what}_total", **{what: how}).inc()
    except Exception:  # noqa: BLE001 — telemetry must not fail the fit
        pass


def _bitset_words_of_rows(bits_k, rows):
    """word[w, i] = bits_k[w, rows[w, i] >> 5] for a [W, BW] bitset table and
    [W, n] bin ids. Up to ``_ROUTE_SELECT_MAX_WORDS`` words it is a select
    over the words, elementwise with a [W, 1] broadcast; wider bitsets (int16
    and int32 bins) keep the per-element gather."""
    BW = bits_k.shape[1]
    widx = rows >> 5
    if BW > _ROUTE_SELECT_MAX_WORDS:
        _note_route("lookup", "gather")
        return jnp.take_along_axis(bits_k, widx, axis=1)
    _note_route("lookup", "select")
    word = jnp.broadcast_to(bits_k[:, :1], rows.shape)
    for j in range(1, BW):
        word = jnp.where(widx == j, bits_k[:, j:j + 1], word)
    return word


@jax.named_scope("gbdt_route")
def _route_rows_to_children(binned_t, row_node, slots, do, feats, bins_,
                            bits_k, lid, is_cat, sibling_derived=False):
    """Row routing of batched growth (leafwise rounds and depthwise levels):
    a row's candidate found once. The ``[W, n]`` work is elementwise (W = 8
    fills a vector register's sublanes: a walk over ``[1, n]`` vectors fills
    one of eight and measured 42-63 ms a round at the cells' rows, PERF.md,
    PR 38) and ends in ONE reduction over ``W``, the row's *code*: ``2w`` if
    candidate ``w`` (a node slot in ``slots`` with ``do`` set; slots are
    distinct, ``-1`` marks an inactive one and matches no row) holds the row
    and the row goes left, ``2w + 1`` if it goes right, ``-1`` if no
    candidate holds it. A candidate's test is ``row <= bin``, or the
    bitset's bit through ``_bitset_words_of_rows``, chosen by a [W, F]
    one-hot test of ``is_cat`` (no gather, however small). One pass over
    ``[n]`` vectors then reads both results from the code and ``row_node``.

    Returns ``(new_row_node, child_pos)``: rows a candidate holds move to its
    child slots ``lid[w]`` / ``lid[w] + 1``; ``child_pos`` is the row's
    position in the round's histogram pass. Both children summed: the code
    itself, in ``[0, 2W)``, ``2w`` = left child of candidate ``w``. The
    sibling derived (``sibling_derived``): ``w`` for a row that goes left,
    and a row that goes right rides the pass at no position (``-1``), like
    one outside the frontier.
    """
    W = slots.shape[0]
    _note_route("fetch", "gather")
    # widen narrow bin storage once into a [W, n] transient (W is small)
    rows = binned_t[feats].astype(jnp.int32)         # [W, n]
    goleft = rows <= bins_[:, None]
    if is_cat is not None:
        word = _bitset_words_of_rows(bits_k, rows)
        member = ((word >> (rows.astype(jnp.uint32) & 31)) & 1).astype(bool)
        cat_k = jnp.any((feats[:, None] == jnp.arange(is_cat.shape[0]))
                        & is_cat[None, :], axis=1)
        goleft = jnp.where(cat_k[:, None], member, goleft)
    # a candidate that does not split holds no row
    holds = row_node[None, :] == jnp.where(do, slots, -1)[:, None]
    two_w = 2 * jnp.arange(W, dtype=jnp.int32)[:, None]
    # the one reduction: at most one candidate holds a row, so the sum over
    # W of "code + 1 where held, else 0" is that candidate's
    code = jnp.sum(jnp.where(holds, jnp.where(goleft, two_w + 1, two_w + 2),
                             0), axis=0) - 1
    # the candidate's child slot by a select over W scalars: ``lid`` follows
    # ``do``, which is no prefix of a depthwise level's candidates
    held_by = code >> 1
    child = jnp.broadcast_to(lid[0], code.shape)
    for w in range(1, W):
        child = jnp.where(held_by == w, lid[w], child)
    new_row_node = jnp.where(code >= 0, child + (code & 1), row_node)
    if sibling_derived:
        # w where the code is 2w; -1 where it is odd (2w + 1, or -1)
        code = (code >> 1) | -(code & 1)
    return new_row_node, code


class Tree(NamedTuple):
    """Fixed-shape tree: node slot 0 is the root; unused slots are inert leaves."""
    feat: jnp.ndarray       # [M] int32 split feature (internal nodes)
    thr_bin: jnp.ndarray    # [M] int32 split bin ("go left if bin <= thr")
    left: jnp.ndarray       # [M] int32 child ids
    right: jnp.ndarray      # [M] int32
    is_leaf: jnp.ndarray    # [M] bool
    leaf_value: jnp.ndarray  # [M] f32 (shrinkage already applied)
    node_count: jnp.ndarray  # [] int32 — nodes actually allocated
    node_grad: jnp.ndarray  # [M] f32 sum of gradients in node (for importances)
    node_hess: jnp.ndarray  # [M] f32
    node_cnt: jnp.ndarray   # [M] f32
    split_gain: jnp.ndarray  # [M] f32 gain of the split at internal nodes
    node_value: jnp.ndarray  # [M] f32 expected value at every node (SHAP path)
    cat_bitset: jnp.ndarray  # [M, BW] uint32 left-subset bitset (categorical
    #                          splits; all-zero rows are numeric splits)


def grow_tree(binned_t: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
              valid: jnp.ndarray, feat_mask: jnp.ndarray, cfg: GrowConfig,
              axis_name: Optional[str] = None,
              is_cat: Optional[jnp.ndarray] = None, qkey=None,
              run_tally: Optional[list] = None):
    """Grow one tree on (possibly sharded) rows.

    binned_t: [F, n] int32/int16/uint8 (column-major); grad/hess: [n] f32; valid: [n] f32
    row mask (0 for padding / bagged-out rows); feat_mask: [F] bool
    (feature_fraction). With ``axis_name`` set (inside shard_map), histograms
    are psum'd so every shard takes identical split decisions —
    data_parallel GBDT semantics. ``run_tally``: a list that the tree's run
    tally (:func:`run_tally_layout`) is appended to; a caller that takes
    none stages the program without it.
    """
    F, n = binned_t.shape
    L = int(cfg.num_leaves)
    M = 2 * L - 1
    B = int(cfg.num_bins)
    BW = bitset_words(B)

    vm = valid.astype(jnp.float32)
    base_t = jnp.stack([grad * vm, hess * vm, vm], axis=0)   # [3, n]
    bl, rpb = _hist_block_geometry(cfg, axis_name, n)
    qscales = None
    if cfg.quantized_grad:
        base_t, qscales = _quantize_for(cfg, base_t, qkey, axis_name, bl,
                                        rpb)

    derive = _sibling_is_derived(qscales is not None)

    @jax.named_scope("gbdt_hist")
    def local_hist(row_pos, W, live=None):
        """This shard's node histograms ``[F, W*3, B]`` (``[bl, F, W*3, B]``,
        one a block, under hist_blocks) of one pass over all rows: f32, or
        where a round derives its siblings the int32 sums, which
        ``global_hist`` scales; and which staged width ran. ``live``: a
        round's count of node positions that hold rows, for the pass to run
        no wider than it must (:func:`_hist_at_width`; the root's pass has
        the one width)."""
        def local(w):
            if bl:
                return _block_node_hists(binned_t, row_pos, base_t, w, B,
                                         qscales, bl, rpb, sums=derive)
            if derive:
                return node_histogram_sums(binned_t, row_pos, base_t, w, B,
                                           quantized=True)
            return node_histogram(binned_t, row_pos, base_t, w, B,
                                  scales=qscales)

        return ((local(W), 0) if live is None else
                _hist_at_width(local, W, live, B, qscales is not None))

    @jax.named_scope("gbdt_hist")
    def global_hist(h, W, per):
        """Global per-node histogram [F, W*3, B] + selected-feature mask of
        ``local_hist``'s; ``per``: how often the pass runs
        (:func:`_allreduce`).

        data_parallel: one full [F, W*3, B] psum — or, under hist_blocks,
        the canonical blocked fold (topology-independent f32 order).
        voting_parallel: vote top_k locally, psum the votes, psum only the
        global top-2k features' histograms (scattered back into a zeroed
        full array so downstream split search keeps static shapes;
        unselected features are masked)."""
        if derive:
            h = dequantize_node_histogram(h, qscales)
        if bl:
            return (_blocked_fold(h, axis_name, "hist", per),
                    jnp.ones(F, dtype=bool))
        if axis_name is None or not cfg.voting:
            h = (_allreduce_round_hist(h, axis_name)
                 if derive and per == "round" else
                 _allreduce(h, axis_name, "hist", per))
            return h, jnp.ones(F, dtype=bool)
        return _voting_select(h, feat_mask, cfg, axis_name, W, per)

    root_local, _ = local_hist(jnp.zeros(n, dtype=jnp.int32), 1)
    root_hist, sel0 = global_hist(root_local, 1, "tree")
    # totals from the raw stats (not the histogram: under voting_parallel an
    # unselected feature's rows are zeroed there). Quantized mode totals the
    # DEQUANTIZED stats so node stats stay consistent with histogram sums;
    # float mode records them for the root and derives nothing from them.
    tot = _stat_totals(base_t, qscales, axis_name, bl, rpb)
    tot_g, tot_h, tot_c = tot[0], tot[1], tot[2]

    # cfg is static Python config: root may split unless max_depth == 0
    root_allow = jnp.bool_(cfg.max_depth < 0 or cfg.max_depth >= 1)
    g0, f0, b0, lg0, lh0, lc0, bits0, right0 = _best_split(
        root_hist, tot_g, tot_h, tot_c, cfg, feat_mask & sel0, root_allow,
        is_cat)
    # float statistics: a candidate's right-side sums ride the cache beside
    # its left-side ones (``crg``/``crh``/``crc``), for the child to take
    float_sums = right0 is not None

    zi = jnp.zeros(M, dtype=jnp.int32)
    zf = jnp.zeros(M, dtype=jnp.float32)
    zbits = jnp.zeros((M, BW), dtype=jnp.uint32)
    state = dict(
        row_node=jnp.zeros(n, dtype=jnp.int32),
        feat=zi, thr=zi, left=zi, right=zi,
        is_leaf=jnp.ones(M, dtype=bool),
        depth=zi,
        ng=zf.at[0].set(tot_g), nh=zf.at[0].set(tot_h), nc=zf.at[0].set(tot_c),
        cg=jnp.full(M, NEG_INF).at[0].set(g0),
        cf=zi.at[0].set(f0), cb=zi.at[0].set(b0),
        clg=zf.at[0].set(lg0), clh=zf.at[0].set(lh0), clc=zf.at[0].set(lc0),
        cbits=zbits.at[0].set(bits0),
        tbits=zbits,
        gain=zf,
        num_nodes=jnp.int32(1),
        # what the tree ran (run_tally_layout): the root's pass so far, one
        # live position and one run
        tally=jnp.zeros(1 + len(run_tally_layout(cfg)),
                        jnp.int32).at[:2].set(1),
    )
    if float_sums:
        _note_float_sums("child_totals")
        state.update(crg=zf.at[0].set(right0[0]), crh=zf.at[0].set(right0[1]),
                     crc=zf.at[0].set(right0[2]))
    if derive:
        # this shard's int32 sums of every node, by node slot, for a round
        # to take its right children from: ``[F, M, 3, B]``, the node axis
        # where a pass has it (under hist_blocks ``[bl, F, M, 3, B]``: the
        # blocks' sums widen to f32 before they fold). Kept from before the
        # reduction, so that voting_parallel's zeroed features are whole in
        # here.
        state["hsum"] = jnp.zeros(
            root_local.shape[:-2] + (M, 3, B), jnp.int32
        ).at[..., 0, :, :].set(root_local)

    # Batched best-first: each round splits the top ``leaf_batch`` pending
    # leaves by cached gain in ONE fused histogram pass (their 2*KB children
    # ride the flat stat axis; the KB left ones where the siblings are
    # derived). Leaves' row sets are disjoint, so batched
    # splits are exactly the splits sequential best-first would take — the
    # only divergence is split ORDER near num_leaves exhaustion (see
    # GrowConfig.leaf_batch). KB=1 reproduces strict sequential growth.
    KB = max(1, min(int(cfg.leaf_batch), L - 1))
    W2 = 2 * KB
    vsplit = jax.vmap(_best_split, in_axes=(0, 0, 0, 0, None, None, 0, None))
    arange_kb = jnp.arange(KB, dtype=jnp.int32)

    def round_work(st):
        top_g, slots = lax.top_k(st["cg"], KB)       # gain-desc candidates
        leaves = (st["num_nodes"] + 1) // 2
        budget = jnp.int32(L) - leaves
        do = (top_g > cfg.min_gain_to_split) & (arange_kb < budget)
        n_split = jnp.sum(do.astype(jnp.int32))
        offset = jnp.cumsum(do.astype(jnp.int32)) - 1
        lid = st["num_nodes"] + 2 * offset           # [KB] child slot ids
        rid = lid + 1

        feats = st["cf"][slots]
        bins_ = st["cb"][slots]
        bits_k = st["cbits"][slots]                  # [KB, BW]

        # rows to their children, and each row's position in the round's
        # pass (both children summed: in [0, 2*KB); the sibling derived: in
        # [0, KB), the left children's)
        new_row_node, child_pos = _route_rows_to_children(
            binned_t, st["row_node"], slots, do, feats, bins_, bits_k, lid,
            is_cat, sibling_derived=derive)

        # ``do`` is a prefix of the gain-sorted candidates, so every live
        # position is under n_split (2 * n_split with both children summed)
        live = n_split if derive else 2 * n_split
        if derive:
            left, which = local_hist(child_pos, KB, live=live)
            kids = _derive_siblings(left, st["hsum"], slots, do)
            h = kids.reshape(kids.shape[:-3] + (3 * W2, B))
        else:
            h, which = local_hist(child_pos, W2, live=live)
        h, sel = global_hist(h, W2, "round")               # [F, W2*3, B]
        hw = h.reshape(F, W2, 3, B).transpose(1, 0, 2, 3)  # [W2,F,3,B]

        # child totals, left from the candidate cache. Quantized: right =
        # parent - left, exact. Float: the right-side sums the candidate was
        # scored with, never a difference at the parent's magnitude
        lg = st["clg"][slots]
        lh = st["clh"][slots]
        lc = st["clc"][slots]

        def pair(left, parent, right):
            r = (st[right][slots] if float_sums
                 else st[parent][slots] - left)
            return jnp.stack([left, r], 1).reshape(-1)               # [W2]

        tg = pair(lg, "ng", "crg")
        th = pair(lh, "nh", "crh")
        tc = pair(lc, "nc", "crc")
        child_depth = st["depth"][slots] + 1         # [KB]
        can_split = jnp.where(cfg.max_depth < 0, True,
                              child_depth + 1 <= cfg.max_depth)
        allow2 = jnp.repeat(can_split & do, 2)
        g2, f2, b2, lg2, lh2, lc2, bits2, right2 = vsplit(
            hw, tg, th, tc, cfg, feat_mask & sel, allow2, is_cat)

        new = dict(st)
        new["row_node"] = new_row_node
        new["tally"] = st["tally"].at[0].add(live).at[2 + which].add(1)

        with jax.named_scope("gbdt_tree_update"):
            # record splits; index M is out of bounds -> dropped for non-splits
            pslot = jnp.where(do, slots, M)
            cslot = jnp.where(jnp.repeat(do, 2),
                              jnp.stack([lid, rid], 1).reshape(-1), M)
            cdep2 = jnp.repeat(child_depth, 2)
            new["feat"] = st["feat"].at[pslot].set(feats, mode="drop")
            new["thr"] = st["thr"].at[pslot].set(bins_, mode="drop")
            new["left"] = st["left"].at[pslot].set(lid, mode="drop")
            new["right"] = st["right"].at[pslot].set(rid, mode="drop")
            new["is_leaf"] = st["is_leaf"].at[pslot].set(False, mode="drop")
            new["gain"] = st["gain"].at[pslot].set(top_g, mode="drop")
            new["tbits"] = st["tbits"].at[pslot].set(bits_k, mode="drop")
            new["depth"] = st["depth"].at[cslot].set(cdep2, mode="drop")
            new["ng"] = st["ng"].at[cslot].set(tg, mode="drop")
            new["nh"] = st["nh"].at[cslot].set(th, mode="drop")
            new["nc"] = st["nc"].at[cslot].set(tc, mode="drop")
            new["cg"] = (st["cg"].at[pslot].set(NEG_INF, mode="drop")
                         .at[cslot].set(g2, mode="drop"))
            new["cf"] = st["cf"].at[cslot].set(f2, mode="drop")
            new["cb"] = st["cb"].at[cslot].set(b2, mode="drop")
            new["clg"] = st["clg"].at[cslot].set(lg2, mode="drop")
            new["clh"] = st["clh"].at[cslot].set(lh2, mode="drop")
            new["clc"] = st["clc"].at[cslot].set(lc2, mode="drop")
            new["cbits"] = st["cbits"].at[cslot].set(bits2, mode="drop")
            if float_sums:
                for k, r in zip(("crg", "crh", "crc"), right2):
                    new[k] = st[k].at[cslot].set(r, mode="drop")
            if derive:
                new["hsum"] = st["hsum"].at[..., cslot, :, :].set(
                    kids, mode="drop")
            new["num_nodes"] = st["num_nodes"] + 2 * n_split
        return new

    def round_body(_, st):
        # skip finished rounds (budget spent / no positive-gain candidate):
        # the static trip count below covers the worst case of one split per
        # round, so batched runs leave most rounds as this cheap no-op. The
        # predicate is identical on every shard (histograms are psum'd), so
        # the branch cannot diverge under shard_map.
        pred = ((st["num_nodes"] < jnp.int32(M))
                & (jnp.max(st["cg"]) > cfg.min_gain_to_split))
        return lax.cond(pred, round_work, lambda s: s, st)

    state = lax.fori_loop(0, L - 1, round_body, state)

    if cfg.quantized_grad and cfg.quant_renew_leaf:
        state = _renew_leaf_stats(state, grad, hess, vm, M, axis_name,
                                  bl, rpb)

    lr = jnp.float32(cfg.learning_rate)
    raw_val = -_soft_threshold(state["ng"], cfg.lambda_l1) / (
        state["nh"] + cfg.lambda_l2 + 1e-38)
    if cfg.max_delta_step > 0:
        raw_val = jnp.clip(raw_val, -cfg.max_delta_step, cfg.max_delta_step)
    leaf_value = jnp.where(state["is_leaf"] & (state["nc"] > 0), raw_val * lr, 0.0)
    node_value = jnp.where(state["nc"] > 0, raw_val * lr, 0.0)

    tree = Tree(
        feat=state["feat"], thr_bin=state["thr"], left=state["left"],
        right=state["right"], is_leaf=state["is_leaf"], leaf_value=leaf_value,
        node_count=state["num_nodes"], node_grad=state["ng"],
        node_hess=state["nh"], node_cnt=state["nc"], split_gain=state["gain"],
        node_value=node_value, cat_bitset=state["tbits"])
    if run_tally is not None:
        run_tally.append(state["tally"])
    # row_node is each row's final leaf: leaf_value[row_node] is this tree's
    # prediction for the training rows — no traversal needed during boosting.
    return tree, state["row_node"]


@jax.named_scope("gbdt_renew_leaf")
def _renew_leaf_stats(state, grad, hess, vm, M: int, axis_name,
                      blocks_local: int = 0, rows_per_block: int = 0):
    """Full-precision leaf-stat renewal for quantized training (LightGBM
    quant_train_renew_leaf): leaf grad/hess/count sums recomputed from the
    original f32 stats by one segment-sum over the final row->leaf map, so
    leaf VALUES carry no int8 quantization error while split structure keeps
    the 2x-rate int8 histogram path. Internal-node stats stay as recorded
    (structural metadata only). Under hist_blocks the segment-sums run per
    canonical block and fold in block order, like every other row
    reduction."""
    seg = state["row_node"]
    stats = jnp.stack([grad * vm, hess * vm, vm])            # [3, n]
    if blocks_local:
        parts = jnp.stack([
            jnp.zeros((3, M), jnp.float32).at[
                :, seg[j * rows_per_block:(j + 1) * rows_per_block]].add(
                stats[:, j * rows_per_block:(j + 1) * rows_per_block])
            for j in range(blocks_local)])
        renew = _blocked_fold(parts, axis_name, "renew")
    else:
        renew = _allreduce(
            jnp.zeros((3, M), jnp.float32).at[:, seg].add(stats), axis_name,
            "renew")
    for i, k in enumerate(("ng", "nh", "nc")):
        state[k] = jnp.where(state["is_leaf"], renew[i], state[k])
    return state


def grow_tree_depthwise(binned_t: jnp.ndarray, grad: jnp.ndarray,
                        hess: jnp.ndarray, valid: jnp.ndarray,
                        feat_mask: jnp.ndarray, cfg: GrowConfig,
                        axis_name: Optional[str] = None,
                        is_cat: Optional[jnp.ndarray] = None, qkey=None,
                        run_tally: Optional[list] = None):
    """Level-synchronous growth: one histogram pass per level.

    Every node on the level frontier contributes 3 stat channels
    (grad/hess/count x node one-hot), so a single MXU histogram pass covers
    the whole level, staged at the level's own width (a pass pays for the
    stat axis by the operand tile, see :func:`_pass_widths`), making a
    31-leaf tree ~6 passes instead of the 30 sequential passes of
    best-first growth. The ``num_leaves`` budget is respected by ranking the
    level's candidate splits by gain. Same Tree layout / slot allocation
    discipline as ``grow_tree`` (slot ids in allocation order), and the same
    ``run_tally``.
    """
    F, n = binned_t.shape
    L = int(cfg.num_leaves)
    M = 2 * L - 1
    B = int(cfg.num_bins)
    BW = bitset_words(B)
    level_widths = _level_widths(cfg)

    vm = valid.astype(jnp.float32)
    base_t = jnp.stack([grad * vm, hess * vm, vm], axis=0)   # [3, n]
    bl, rpb = _hist_block_geometry(cfg, axis_name, n)
    qscales = None
    if cfg.quantized_grad:
        base_t, qscales = _quantize_for(cfg, base_t, qkey, axis_name, bl,
                                        rpb)
    zi = jnp.zeros(M, dtype=jnp.int32)
    zf = jnp.zeros(M, dtype=jnp.float32)
    tree_arrays = dict(
        feat=zi, thr=zi, left=zi, right=zi,
        is_leaf=jnp.ones(M, dtype=bool), gain=zf,
        ng=zf, nh=zf, nc=zf,
        bits=jnp.zeros((M, BW), dtype=jnp.uint32))

    row_node = jnp.zeros(n, dtype=jnp.int32)
    num_nodes = jnp.int32(1)
    leaves = jnp.int32(1)

    # root totals (dequantized sums: consistent with histogram sums)
    tot0 = _stat_totals(base_t, qscales, axis_name, bl, rpb)
    tree_arrays["ng"] = tree_arrays["ng"].at[0].set(tot0[0])
    tree_arrays["nh"] = tree_arrays["nh"].at[0].set(tot0[1])
    tree_arrays["nc"] = tree_arrays["nc"].at[0].set(tot0[2])

    # frontier: node slot ids at the current level (-1 = inactive slot)
    frontier = jnp.full(L, -1, dtype=jnp.int32).at[0].set(0)

    vsplit = jax.vmap(_best_split, in_axes=(0, 0, 0, 0, None, None, 0, None))

    def make_level(depth: int, W: int):
        def level_work(state):
            row_node, frontier, num_nodes, leaves, tree_arrays, tally = state
            fr = frontier[:W]
            active = fr >= 0
            # what the tree ran (run_tally_layout): this level's pass and the
            # frontier positions that hold nodes
            tally = tally.at[0].add(jnp.sum(active.astype(jnp.int32))).at[
                1 + depth].add(1)

            # per-row frontier position (rows at finished leaves get -1);
            # index M is out of bounds -> dropped for inactive slots
            slot_to_pos = jnp.full(M, -1, dtype=jnp.int32)
            slot_to_pos = slot_to_pos.at[jnp.where(active, fr, M)].set(
                jnp.arange(W, dtype=jnp.int32), mode="drop")
            row_pos = slot_to_pos[row_node]      # [n] in [-1, W)

            # one fused histogram pass covers the whole level: the
            # row->position one-hot and masked stats are built in VMEM
            feat_mask_lvl = feat_mask
            with jax.named_scope("gbdt_hist"):
                if bl:
                    # canonical blocked fold: topology-independent f32 order
                    h = _blocked_node_hist(binned_t, row_pos, base_t, W, B,
                                           qscales, bl, rpb, axis_name,
                                           "level")
                else:
                    h = node_histogram(binned_t, row_pos, base_t, W, B,
                                       scales=qscales)         # [F, W*3, B]
                    if axis_name is not None and cfg.voting:
                        # per-level voting: shards vote top_k features by
                        # their best local gain across the WHOLE frontier,
                        # then only the global top-2k features' level
                        # histograms cross the interconnect
                        h, sel = _voting_select(h, feat_mask, cfg,
                                                axis_name, W, "level")
                        feat_mask_lvl = feat_mask & sel
                    else:
                        h = _allreduce(h, axis_name, "hist", "level")
            h = h.reshape(F, W, 3, B).transpose(1, 0, 2, 3)      # [W,F,3,B]

            tot = jnp.stack([tree_arrays["ng"][jnp.maximum(fr, 0)],
                             tree_arrays["nh"][jnp.maximum(fr, 0)],
                             tree_arrays["nc"][jnp.maximum(fr, 0)]],
                            axis=1)                                    # [W, 3]

            allow = active & jnp.bool_(cfg.max_depth < 0
                                       or depth + 1 <= cfg.max_depth)
            gains, feats, bins_, lgs, lhs, lcs, bits_w, rights = vsplit(
                h, tot[:, 0], tot[:, 1], tot[:, 2], cfg, feat_mask_lvl,
                allow, is_cat)
            gains = jnp.where(active, gains, NEG_INF)

            # budget: leaves + #splits <= num_leaves — best gains first
            order = jnp.argsort(-gains)
            rank = jnp.zeros(W, jnp.int32).at[order].set(
                jnp.arange(W, dtype=jnp.int32))
            budget = jnp.int32(L) - leaves
            do = (gains > cfg.min_gain_to_split) & (rank < budget) & active

            # allocate child slots in frontier order among split nodes
            offset = jnp.cumsum(do.astype(jnp.int32)) - 1
            lid = num_nodes + 2 * offset
            rid = lid + 1
            n_split = jnp.sum(do.astype(jnp.int32))

            # update rows: rows in split nodes move to their child slot
            # (keyed on node slot ids — inactive frontier slots are -1 and
            # match no row since row_node >= 0)
            row_node, _ = _route_rows_to_children(
                binned_t, row_node, jnp.where(active, fr, -1), do, feats,
                bins_, bits_w, lid, is_cat)

            with jax.named_scope("gbdt_tree_update"):
                # record splits into tree arrays; index M (out of bounds) drops
                # the scatter for nodes that don't split
                slot = jnp.where(do, fr, M)
                ta = dict(tree_arrays)
                ta["feat"] = ta["feat"].at[slot].set(feats, mode="drop")
                ta["thr"] = ta["thr"].at[slot].set(bins_, mode="drop")
                ta["left"] = ta["left"].at[slot].set(lid, mode="drop")
                ta["right"] = ta["right"].at[slot].set(rid, mode="drop")
                ta["is_leaf"] = ta["is_leaf"].at[slot].set(False, mode="drop")
                ta["gain"] = ta["gain"].at[slot].set(gains, mode="drop")
                ta["bits"] = ta["bits"].at[slot].set(bits_w, mode="drop")
                # children stats: right = parent - left (quantized, exact)
                # or the winning candidate's own right-side sums (float)
                parent_g, parent_h, parent_c = tot[:, 0], tot[:, 1], tot[:, 2]
                if rights is not None:
                    _note_float_sums("child_totals")
                lslot = jnp.where(do, lid, M)
                rslot = jnp.where(do, rid, M)
                for k, left, parent, i in (("ng", lgs, parent_g, 0),
                                           ("nh", lhs, parent_h, 1),
                                           ("nc", lcs, parent_c, 2)):
                    ta[k] = ta[k].at[lslot].set(left, mode="drop")
                    ta[k] = ta[k].at[rslot].set(
                        parent - left if rights is None else rights[i],
                        mode="drop")

            # next frontier: the children, compacted into 2*W slots
            W_next = min(2 * W, L)
            child_slots = jnp.stack([jnp.where(do, lid, -1),
                                     jnp.where(do, rid, -1)],
                                    axis=1).reshape(-1)
            # compact actives to the front (stable) and pad with -1
            key = jnp.where(child_slots >= 0, 0, 1)
            perm = jnp.argsort(key, stable=True)
            compacted = child_slots[perm]
            frontier = jnp.full(L, -1, dtype=jnp.int32).at[:W_next].set(
                compacted[:W_next])

            return (row_node, frontier, num_nodes + 2 * n_split,
                    leaves + n_split, ta, tally)

        return level_work

    state = (row_node, frontier, num_nodes, leaves, tree_arrays,
             jnp.zeros(1 + len(level_widths), jnp.int32))
    for depth, W in enumerate(level_widths):  # static unroll: W varies by level
        # runtime skip: once the budget is spent or the frontier is empty,
        # the remaining (slack) levels cost nothing
        pred = (state[3] < jnp.int32(L)) & jnp.any(state[1] >= 0)
        state = lax.cond(pred, make_level(depth, W), lambda s: s, state)
    row_node, frontier, num_nodes, leaves, tree_arrays, tally = state
    if run_tally is not None:
        run_tally.append(tally)

    if cfg.quantized_grad and cfg.quant_renew_leaf:
        tree_arrays = _renew_leaf_stats(
            dict(tree_arrays, row_node=row_node), grad, hess, vm, M,
            axis_name, bl, rpb)

    lr = jnp.float32(cfg.learning_rate)
    raw_val = -_soft_threshold(tree_arrays["ng"], cfg.lambda_l1) / (
        tree_arrays["nh"] + cfg.lambda_l2 + 1e-38)
    if cfg.max_delta_step > 0:
        raw_val = jnp.clip(raw_val, -cfg.max_delta_step, cfg.max_delta_step)
    leaf_value = jnp.where(tree_arrays["is_leaf"] & (tree_arrays["nc"] > 0),
                           raw_val * lr, 0.0)
    node_value = jnp.where(tree_arrays["nc"] > 0, raw_val * lr, 0.0)

    tree = Tree(
        feat=tree_arrays["feat"], thr_bin=tree_arrays["thr"],
        left=tree_arrays["left"], right=tree_arrays["right"],
        is_leaf=tree_arrays["is_leaf"], leaf_value=leaf_value,
        node_count=num_nodes, node_grad=tree_arrays["ng"],
        node_hess=tree_arrays["nh"], node_cnt=tree_arrays["nc"],
        split_gain=tree_arrays["gain"], node_value=node_value,
        cat_bitset=tree_arrays["bits"])
    return tree, row_node


def predict_tree_binned(tree: Tree, binned_t: jnp.ndarray,
                        is_cat: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Evaluate one grown tree on binned rows: ``[F, n]`` (column-major, the
    training matrix's layout) -> ``[n]`` leaf values.

    A walk over the tree's allocated node slots in order, not over the rows'
    depth: the growers hand out child slots above their parent's, so by the
    time slot ``j`` is reached every row that will ever sit on it does, and
    one pass moves them to its children. A step reads scalars of node ``j``
    (no ``table[node]`` over the rows), one row of the matrix
    (``dynamic_index_in_dim``, no ``take_along_axis``) and tests a category
    through the select chain that training's routing uses
    (``_bitset_words_of_rows``), so the whole scorer is ``node_count``
    elementwise passes over ``n`` rows with no per-row gather, and a tree
    that stopped at 11 nodes costs 11 of them."""
    n = binned_t.shape[1]

    def body(j, carry):
        node, value = carry
        x = lax.dynamic_index_in_dim(binned_t, tree.feat[j], 0,
                                     keepdims=False).astype(jnp.int32)
        go_left = x <= tree.thr_bin[j]
        if is_cat is not None:
            word = _bitset_words_of_rows(
                lax.dynamic_index_in_dim(tree.cat_bitset, j, 0), x[None, :])[0]
            member = ((word >> (x.astype(jnp.uint32) & 31)) & 1).astype(bool)
            go_left = jnp.where(is_cat[tree.feat[j]], member, go_left)
        here = node == j
        # a row's value is that of the last slot it sat on, which is its leaf
        value = jnp.where(here, tree.leaf_value[j], value)
        nxt = jnp.where(go_left, tree.left[j], tree.right[j])
        return jnp.where(here & ~tree.is_leaf[j], nxt, node), value

    with jax.named_scope("gbdt_valid_score"):
        return lax.fori_loop(
            0, tree.node_count, body,
            (jnp.zeros(n, jnp.int32), jnp.zeros(n, tree.leaf_value.dtype)))[1]


def raw_to_cat_bin(x: jnp.ndarray, max_bin_idx: int) -> jnp.ndarray:
    """Raw categorical value -> bin id: round-to-nearest, NaN/negative -> 0
    (matching the binner's 0.5-boundary categorical bins)."""
    b = jnp.where(jnp.isnan(x), 0.0, jnp.floor(x + 0.5))
    return jnp.clip(b, 0, max_bin_idx).astype(jnp.int32)


def cat_member(bits_rows: jnp.ndarray, x: jnp.ndarray, max_bin_idx: int,
               strict: bool) -> jnp.ndarray:
    """Categorical membership for raw values.

    ``strict=False`` (models trained HERE): ids bin exactly as training did
    — NaN/negative -> bin 0, out-of-range clips into the catch-all bin.
    ``strict=True`` (models imported from stock LightGBM, which has no
    catch-all): FindInBitset semantics — NaN or any id outside the bitset
    routes right (non-member).
    """
    if not strict:
        return bit_test(bits_rows, raw_to_cat_bin(x, max_bin_idx))
    b = jnp.where(jnp.isnan(x), -1.0, jnp.floor(x + 0.5))
    in_range = (b >= 0) & (b <= max_bin_idx)
    cbin = jnp.clip(b, 0, max_bin_idx).astype(jnp.int32)
    return bit_test(bits_rows, cbin) & in_range


def predict_forest_raw(trees, thr_raw, features: jnp.ndarray,
                       depth_cap: int,
                       is_cat: Optional[jnp.ndarray] = None,
                       cat_max_bin: int = 0,
                       missing_dec: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
    """Evaluate a stacked forest on RAW float features.

    trees: Tree of arrays stacked on a leading [T] axis; thr_raw: [T, M] f32 raw
    thresholds ("go left if x <= thr", NaN goes left — matching the binning
    convention of NaN -> bin 0). Categorical features (``is_cat``) route by
    bitset membership of the rounded category id. features: [n, F].
    Returns [T, n].

    ``missing_dec`` ([T, M] per-node LightGBM decision_type bytes) switches
    numerical routing to stock LightGBM's NumericalDecision semantics
    (lightgbm tree.h): NaN maps to 0.0 unless the node's missing type is
    NaN; zero-as-missing and NaN-missing route to the stored default side;
    everything else compares ``x <= thr``. Needed for imported models —
    the framework's own training always writes decision_type 10
    (default-left, NaN missing), which equals the fast default path.
    """
    n = features.shape[0]

    def one_tree(tree_slice, thr, mdec):
        node = jnp.zeros(n, dtype=jnp.int32)
        # clip to the BINNER's last bin (the training-time catch-all), not
        # the bitset word boundary — out-of-range ids must route exactly as
        # they did during training. Imported stock-LightGBM models
        # (cat_max_bin == 0) have no catch-all: out-of-range routes right.
        strict = cat_max_bin <= 0
        max_bin_idx = (cat_max_bin - 1 if cat_max_bin > 0
                       else tree_slice.cat_bitset.shape[-1] * 32 - 1)

        def body(_, node):
            f = tree_slice.feat[node]
            t = thr[node]
            x = jnp.take_along_axis(features, f[:, None], axis=1)[:, 0]
            if mdec is None:
                go_left = ~(x > t)  # NaN compares false -> goes left
            else:
                md = mdec[node]
                mt = (md >> 2) & 3          # 0 none, 1 zero, 2 NaN
                dl = (md & 2) != 0          # default-left
                x_nan = jnp.isnan(x)
                xv = jnp.where(x_nan & (mt != 2), 0.0, x)
                # stock Tree::IsZero: |x| <= kZeroThreshold (1e-35), not
                # exact equality
                is_zero = jnp.abs(xv) <= jnp.float32(1e-35)
                use_default = (((mt == 1) & is_zero) | ((mt == 2) & x_nan))
                go_left = jnp.where(use_default, dl, ~(xv > t))
            if is_cat is not None:
                # int8 predict lane: features arrive as integer bin ids
                # (quantize.quantize_features); category routing widens to
                # f32 — bin id == category id under the binner's identity
                # bins, exact for ids < 256
                xc = (x.astype(jnp.float32)
                      if jnp.issubdtype(x.dtype, jnp.integer) else x)
                go_left = jnp.where(
                    is_cat[f],
                    cat_member(tree_slice.cat_bitset[node], xc, max_bin_idx,
                               strict),
                    go_left)
            nxt = jnp.where(go_left, tree_slice.left[node], tree_slice.right[node])
            return jnp.where(tree_slice.is_leaf[node], node, nxt)

        node = lax.fori_loop(0, depth_cap, body, node)
        return tree_slice.leaf_value[node]

    if missing_dec is None:
        return jax.vmap(lambda ts, th: one_tree(ts, th, None))(
            trees, thr_raw)
    return jax.vmap(one_tree)(trees, thr_raw, missing_dec)
