"""Float statistics at a root of 68 M rows: the sums of tree growth against
float64, on data built to have the fault's shape at a size a CPU test can
afford (PERF.md, PR 33).

The fault: a node whose hessian and count totals pass 2^22 and 2^24, where
f32 numbers are 0.5 and 2 apart, with a best split that leaves a child of
hessian about 10 and count 30. A right side taken as ``total - left``, and a
right child taken as ``parent - left``, are then good to the parent's
spacing, which is 5% of the child. The float path sums each side from its own
bins instead (``growth._best_split``); the quantized path keeps the
subtraction, which int32 sums make exact. ``parent_best_split`` below is the
arithmetic this replaced, kept as the negative control: every comparison that
the program passes, it has to fail.
"""

import ml_dtypes
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.models.gbdt import growth
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.parallel import mesh as meshlib
from mmlspark_tpu.parallel.compat import shard_map
from mmlspark_tpu.parallel.placement import pspec

# -- the parent's arithmetic --------------------------------------------------


def parent_best_split(hist, tot_g, tot_h, tot_c, cfg, feat_mask, allow,
                      is_cat=None):
    """``growth._best_split`` as it was before PR 33, for float and quantized
    statistics alike: right = total - left; ``right`` None, so the growers
    hand a right child ``parent - left``."""
    B = hist.shape[-1]
    g, h, c = hist[:, 0, :], hist[:, 1, :], hist[:, 2, :]
    gl = jnp.cumsum(g, axis=-1)
    hl = jnp.cumsum(h, axis=-1)
    cl = jnp.cumsum(c, axis=-1)
    prefix_ok = jnp.ones((hist.shape[0], B), dtype=bool)
    rank = None
    if is_cat is not None:
        ratio = jnp.where(c > 0, g / (h + cfg.cat_smooth), jnp.inf)
        order = jnp.argsort(ratio, axis=-1)
        rank = jnp.zeros_like(order).at[
            jnp.arange(order.shape[0])[:, None], order].set(
            jnp.broadcast_to(jnp.arange(B), order.shape))
        icat = is_cat[:, None]
        gl = jnp.where(icat, jnp.cumsum(
            jnp.take_along_axis(g, order, axis=-1), axis=-1), gl)
        hl = jnp.where(icat, jnp.cumsum(
            jnp.take_along_axis(h, order, axis=-1), axis=-1), hl)
        cl = jnp.where(icat, jnp.cumsum(
            jnp.take_along_axis(c, order, axis=-1), axis=-1), cl)
        prefix_ok = jnp.where(
            icat, jnp.arange(B)[None, :] < int(cfg.max_cat_threshold),
            prefix_ok)
    gr, hr, cr = tot_g - gl, tot_h - hl, tot_c - cl
    gain = (growth._leaf_objective(gl, hl, cfg)
            + growth._leaf_objective(gr, hr, cfg)
            - growth._leaf_objective(tot_g, tot_h, cfg))
    ok = ((cl >= cfg.min_data_in_leaf) & (cr >= cfg.min_data_in_leaf)
          & (hl >= cfg.min_sum_hessian_in_leaf)
          & (hr >= cfg.min_sum_hessian_in_leaf)
          & feat_mask[:, None] & allow & prefix_ok)
    ok = ok.at[:, B - 1].set(False)
    gain = jnp.where(ok, gain, growth.NEG_INF)
    flat = jnp.argmax(gain)
    f, b = flat // B, flat % B
    if is_cat is None:
        bits = jnp.zeros(growth.bitset_words(B), dtype=jnp.uint32)
    else:
        bits = growth._pack_bits(is_cat[f] & (rank[f] <= b))
    return (gain[f, b], f.astype(jnp.int32), b.astype(jnp.int32),
            gl[f, b], hl[f, b], cl[f, b], bits, None)


# -- (a) split search on a synthetic histogram --------------------------------

B_HIST = 64


def _fault_histogram(seed):
    """[3, 3, B] f32: feature 0 holds one heavy bin (hessian 4.5e6, 2e7
    rows), 32 light ones of the same gradient ratio, and a tail of 30 rows
    whose ratio is far off: the best split cuts the tail off. Features 1 and
    2 hold the same rows mixed evenly, so they offer nothing."""
    rng = np.random.default_rng(seed)
    h = np.zeros(B_HIST)
    c = np.zeros(B_HIST)
    h[0], c[0] = 4.5e6 + rng.uniform(0, 1), 2.0e7 + 3
    h[1:33] = rng.uniform(25, 35, 32)
    c[1:33] = np.round(h[1:33] * 30)
    c[33:63] = 1.0
    h[33:63] = rng.uniform(0.30, 0.38, 30)
    g = -0.002 * h * rng.uniform(0.999, 1.001, B_HIST)
    g[33:63] = 0.9 * h[33:63]
    hist = np.zeros((3, 3, B_HIST), np.float32)
    hist[0] = np.stack([g, h, c])
    for f in (1, 2):
        w = rng.dirichlet(np.full(B_HIST, 50.0))
        hist[f] = np.stack([g.sum() * w, h.sum() * w,
                            np.round(c.sum() * w)])
    return hist


def _float64_search(hist, cfg, cat):
    """The best split of ``hist`` in float64: (feature, candidate, left
    [3], right [3], gain), a categorical feature's bins taken in the order
    of their smoothed ratio."""
    hist = hist.astype(np.float64)
    best = (-np.inf,)
    for f in range(hist.shape[0]):
        g, h, c = hist[f]
        if cat[f]:
            order = np.argsort(np.where(c > 0, g / (h + cfg.cat_smooth),
                                        np.inf), kind="stable")
            g, h, c = g[order], h[order], c[order]
        for b in range(B_HIST - 1):
            if cat[f] and b >= cfg.max_cat_threshold:
                break
            left = np.array([g[:b + 1].sum(), h[:b + 1].sum(),
                             c[:b + 1].sum()])
            right = np.array([g[b + 1:].sum(), h[b + 1:].sum(),
                              c[b + 1:].sum()])
            if min(left[2], right[2]) < cfg.min_data_in_leaf or \
                    min(left[1], right[1]) < cfg.min_sum_hessian_in_leaf:
                continue
            gain = (left[0] ** 2 / left[1] + right[0] ** 2 / right[1]
                    - (left[0] + right[0]) ** 2 / (left[1] + right[1]))
            if gain > best[0]:
                best = (gain, f, b, left, right)
    gain, f, b, left, right = best
    return f, b, left, right, gain


def _search_agrees(split, hist, cfg, cat):
    """Whether ``split`` picked float64's candidate with both sides' G, H and
    count and the gain good to 1e-5 of their own (so the small side to 1e-5
    of the small side)."""
    tot = hist[0].astype(np.float64).sum(axis=1)
    is_cat = jnp.asarray(cat) if any(cat) else None
    gain, f, b, lg, lh, lc, _, right = jax.jit(
        lambda x: split(x, *(jnp.float32(t) for t in tot), cfg,
                        jnp.ones(3, bool), jnp.bool_(True), is_cat))(
        jnp.asarray(hist))
    if right is None:                      # the parent's: parent - left
        right = (jnp.float32(tot[0]) - lg, jnp.float32(tot[1]) - lh,
                 jnp.float32(tot[2]) - lc)
    f64, b64, left64, right64, gain64 = _float64_search(hist, cfg, cat)
    got = np.array([float(x) for x in (lg, lh, lc, *right, gain)])
    want = np.array([*left64, *right64, gain64])
    return ((int(f), int(b)) == (f64, b64)
            and bool(np.all(np.abs(got - want) <= 1e-5 * np.abs(want))))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", ["numeric", "categorical"])
def test_split_search_agrees_with_float64_and_the_parents_does_not(kind,
                                                                   seed):
    hist = _fault_histogram(seed)
    assert hist[0, 1].sum() > 2 ** 22 and hist[0, 2].sum() > 2 ** 24
    cfg = GrowConfig(num_bins=B_HIST, min_data_in_leaf=20,
                     max_cat_threshold=B_HIST)
    cat = [kind == "categorical", False, False]
    _, _, _, right64, _ = _float64_search(hist, cfg, cat)
    assert 9 < right64[1] < 12 and right64[2] == 30     # the small child
    assert _search_agrees(growth._best_split, hist, cfg, cat)
    assert not _search_agrees(parent_best_split, hist, cfg, cat)


# -- (b) grown trees on weighted rows -----------------------------------------

N_ROWS, N_BINS = 2048, 32


def _fault_rows(seed):
    """Rows whose root has the fault's shape: 1200 rows of weight 2^14 in
    bin 0 of feature 0 (count 2e7, hessian 4.9e6), 800 of weight 1 over bins
    1-20, and 48 over bins 24-31 whose gradients point the other way (hessian
    about 16, and about 10 and 6 once it splits again). Features 1 to 3 are
    noise. Shuffled, so that two shards each hold some of every group."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, N_BINS, (4, N_ROWS))
    w = np.ones(N_ROWS, np.float32)
    grad = np.full(N_ROWS, -0.0005, np.float32)
    hess = np.full(N_ROWS, 0.25, np.float32)
    bins[0, :1200], w[:1200] = 0, 2.0 ** 14
    bins[0, 1200:2000] = rng.integers(1, 21, 800)
    grad[1200:2000] += rng.normal(0, 0.002, 800).astype(np.float32)
    bins[0, 2000:] = rng.integers(24, N_BINS, 48)
    hess[2000:] = 0.34
    grad[2000:] = np.where(bins[0, 2000:] < 28, 0.3, 0.1)
    perm = rng.permutation(N_ROWS)
    return (bins[:, perm].astype(np.uint8), grad[perm], hess[perm], w[perm])


def _grow(policy, shards, split, cat, quantized=False, seed=0):
    """One tree on ``_fault_rows`` as numpy arrays, with ``split`` standing
    where ``growth._best_split`` stands."""
    binned, grad, hess, w = _fault_rows(seed)
    cfg = GrowConfig(num_leaves=6, num_bins=N_BINS, min_data_in_leaf=5,
                     growth_policy=policy, leaf_batch=2,
                     max_cat_threshold=N_BINS, quantized_grad=quantized,
                     quant_renew_leaf=False)
    grow = (growth.grow_tree_depthwise if policy == "depthwise"
            else growth.grow_tree)
    is_cat = jnp.asarray([True, False, False, False]) if cat else None
    axis = "data" if shards > 1 else None

    def fn(b, g, h, v, fm, key):
        return grow(b, g, h, v, fm, cfg, axis, is_cat, key)[0]

    if shards > 1:
        fn = shard_map(fn, mesh=meshlib.make_mesh(
            devices=jax.devices()[:shards]),
            in_specs=(pspec(None, "data"),) + (pspec("data"),) * 3
            + (pspec(), pspec()), out_specs=pspec(), check_vma=False)
    real = growth._best_split
    growth._best_split = split
    try:
        tree = jax.jit(fn)(jnp.asarray(binned), jnp.asarray(grad),
                           jnp.asarray(hess), jnp.asarray(w),
                           jnp.ones(4, bool), jax.random.PRNGKey(0))
    finally:
        growth._best_split = real
    return jax.tree_util.tree_map(np.asarray, tree), cfg


def _leaf_values_agree(tree, cfg, cat, seed=0):
    """Whether every leaf's value is ``-lr G / H`` of the rows routed to it
    (float64 sums of the bf16-rounded statistics) to 1e-4, and its recorded
    count their count."""
    binned, grad, hess, w = _fault_rows(seed)
    stats = np.stack([grad * w, hess * w, w]).astype(
        ml_dtypes.bfloat16).astype(np.float64)
    node = np.zeros(N_ROWS, np.int64)
    for j in range(int(tree.node_count)):       # a child's slot is above its
        if tree.is_leaf[j]:                     # parent's
            continue
        x = binned[tree.feat[j]].astype(np.int64)
        go_left = x <= tree.thr_bin[j]
        if cat and tree.feat[j] == 0:
            go_left = ((tree.cat_bitset[j][x >> 5] >> (x & 31)) & 1) == 1
        node = np.where(node == j,
                        np.where(go_left, tree.left[j], tree.right[j]), node)
    leaves = [j for j in range(int(tree.node_count)) if tree.is_leaf[j]]
    assert len(leaves) >= 3
    sums = np.array([stats[:, node == j].sum(axis=1) for j in leaves])
    assert sums[:, 1].min() < 20 and sums[:, 1].max() > 2 ** 22
    want = -cfg.learning_rate * sums[:, 0] / sums[:, 1]
    return bool(
        np.all(np.abs(tree.leaf_value[leaves] - want) <= 1e-4 * np.abs(want))
        and np.all(np.abs(tree.node_cnt[leaves] - sums[:, 2])
                   <= 1e-4 * sums[:, 2]))


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("policy", ["leafwise", "depthwise"])
@pytest.mark.parametrize("kind", ["numeric", "categorical"])
def test_leaf_values_agree_with_float64_and_the_parents_do_not(kind, policy,
                                                               shards):
    cat = kind == "categorical"
    tree, cfg = _grow(policy, shards, growth._best_split, cat)
    assert _leaf_values_agree(tree, cfg, cat)
    tree, cfg = _grow(policy, shards, parent_best_split, cat)
    assert not _leaf_values_agree(tree, cfg, cat)


# -- (d) the quantized path is the parent's, to the bit -----------------------


@pytest.mark.parametrize("policy", ["leafwise", "depthwise"])
@pytest.mark.parametrize("kind", ["numeric", "categorical"])
def test_quantized_trees_are_the_parents_formula_to_the_bit(kind, policy):
    cat = kind == "categorical"
    ours, _ = _grow(policy, 1, growth._best_split, cat, quantized=True)
    parents, _ = _grow(policy, 1, parent_best_split, cat, quantized=True)
    assert int(ours.node_count) >= 5
    for name, a, b in zip(ours._fields, ours, parents):
        assert a.tobytes() == b.tobytes(), name
