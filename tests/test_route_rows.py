"""Row routing of batched tree growth (``growth._route_rows_to_children``).

Since PR 38 the routing finds a row's candidate by one reduction over its
``[W, n]`` work and returns each row's new node slot and its position in the
round's histogram pass. What holds it here:

* NumPy's plain lookup ``bits[idx >> 5] >> (idx & 31) & 1`` at nine bin
  counts, for the node slot and both forms of the position;
* the ``[W, n]`` formula it replaced (``_reference_route``, the routing of
  PRs 27 to 37 with the leafwise caller's position block), under ``jit`` and,
  monkeypatched into whole fits, tree for tree to the bit;
* the lowering: one reduction over ``[W, n]`` and no gather beside the
  feature rows' fetch up to ``growth._ROUTE_SELECT_MAX_WORDS`` bitset words,
  one bitset gather above;
* a fit's own row counts against the predict path (``bit_test``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as pspec

from _gbdt_reference import route_rows_wn as _reference_route
from _gbdt_reference import walk_by_gathers
from mmlspark_tpu.parallel.compat import shard_map
from mmlspark_tpu.models.gbdt import booster as booster_mod
from mmlspark_tpu.models.gbdt import growth
from mmlspark_tpu.models.gbdt.booster import LightGBMDataset, train_booster
from mmlspark_tpu.models.gbdt.growth import (GrowConfig, Tree,
                                             _route_rows_to_children,
                                             bitset_words,
                                             predict_tree_binned)
from mmlspark_tpu.observability import metrics
from mmlspark_tpu.parallel import mesh as meshlib

_BOUND_BINS = 32 * growth._ROUTE_SELECT_MAX_WORDS      # widest select chain
_BIN_COUNTS = [31, 32, 33, 63, 64, 255, 256, _BOUND_BINS, _BOUND_BINS + 1]


def _case(num_bins, seed, n=1500, F=9, W=6):
    """Random candidates: numeric and categorical features mixed, a ``do``
    that is no prefix, one inactive slot (-1, as a depthwise frontier
    pads)."""
    rng = np.random.default_rng(seed)
    dtype = np.uint8 if num_bins <= 256 else np.int16
    binned_t = rng.integers(0, num_bins, (F, n)).astype(dtype)
    binned_t[:, :4] = num_bins - 1                      # the last bit is hit
    binned_t[:, 4:8] = 0
    slots = rng.permutation(12)[:W].astype(np.int32)
    slots[W - 1] = -1
    row_node = rng.integers(0, 12, n).astype(np.int32)
    do = np.array([True, True, False, True, True, True])[:W]
    is_cat = np.zeros(F, bool)
    is_cat[rng.permutation(F)[:F // 2]] = True
    feats = rng.integers(0, F, W).astype(np.int32)
    feats[0], feats[1] = np.flatnonzero(is_cat)[0], np.flatnonzero(~is_cat)[0]
    bins_ = rng.integers(0, num_bins, W).astype(np.int32)
    bits_k = rng.integers(0, 2 ** 32, (W, bitset_words(num_bins)),
                          dtype=np.uint64).astype(np.uint32)
    lid = (20 + 2 * np.arange(W)).astype(np.int32)
    return binned_t, row_node, slots, do, feats, bins_, bits_k, lid, is_cat


def _np_route(binned_t, row_node, slots, do, feats, bins_, bits_k, lid,
              is_cat, sibling_derived):
    idx = binned_t[feats].astype(np.int64)                          # [W, n]
    goleft = idx <= bins_[:, None]
    if is_cat is not None:
        word = bits_k[np.arange(len(feats))[:, None], idx >> 5]
        member = ((word >> (idx & 31).astype(np.uint32)) & 1).astype(bool)
        goleft = np.where(is_cat[feats][:, None], member, goleft)
    move = (row_node[None, :] == slots[:, None]) & do[:, None]
    new_row_node = row_node.copy()
    child_pos = np.full(row_node.shape, -1, np.int32)
    for w in range(len(feats)):
        left = goleft[w][move[w]]
        new_row_node[move[w]] = np.where(left, lid[w], lid[w] + 1)
        child_pos[move[w]] = (np.where(left, w, -1) if sibling_derived
                              else np.where(left, 2 * w, 2 * w + 1))
    return new_row_node, child_pos


_FETCH = "gather"      # how the feature rows are fetched: the chip's winner
_COUNTERS = (("lookup", "select"), ("lookup", "gather"), ("fetch", _FETCH))


def _counted():
    return {(what, how): metrics.counter(f"gbdt_route_{what}_total",
                                         **{what: how}).value
            for what, how in _COUNTERS}


def _counted_since(before):
    return {k: v - before[k] for k, v in _counted().items()}


@pytest.mark.parametrize("num_bins", _BIN_COUNTS)
def test_route_equals_numpy_bit_lookup(num_bins):
    """The node slot and both forms of the pass position, an inactive slot,
    a ``do`` that is no prefix and mixed candidates in every case; each
    staging counts its fetch and its lookup once."""
    case = _case(num_bins, seed=num_bins)
    for derived in (False, True):
        before = _counted()
        # a lambda of its own: every case traces, whatever shapes it shares
        got = jax.jit(lambda *a: _route_rows_to_children(
            *a, sibling_derived=derived))(*map(jnp.asarray, case))
        want = _np_route(*case, derived)
        for name, g, w in zip(("new_row_node", "child_pos"), got, want):
            np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)
        chain = num_bins <= _BOUND_BINS
        assert _counted_since(before) == {
            ("lookup", "select"): int(chain),
            ("lookup", "gather"): int(not chain), ("fetch", _FETCH): 1}


@pytest.mark.parametrize("num_bins", _BIN_COUNTS)
def test_route_equals_the_formula_it_replaced(num_bins):
    """Against the ``[W, n]`` formula under ``jit``, with and without
    categorical fields, in both forms of the position."""
    *args, is_cat = map(jnp.asarray, _case(num_bins, seed=7 + num_bins))
    for cat in (is_cat, None):
        for derived in (False, True):
            got = jax.jit(lambda *a: _route_rows_to_children(
                *a, cat, sibling_derived=derived))(*args)
            want = jax.jit(lambda *a: _reference_route(
                *a, cat, sibling_derived=derived))(*args)
            for name, g, w in zip(("new_row_node", "child_pos"), got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                              err_msg=name)


def _lowered(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return re.sub(r"module @\S+", "module @m", text, count=1)


def _gathers(text):
    return len(re.findall(r'stablehlo\.(?:dynamic_)?gather"?\(', text))


def _reduces_over(text, shape):
    """Reductions whose operand has the leading dimensions ``shape``."""
    dims = "x".join(map(str, shape))
    return len(re.findall(
        r"stablehlo\.reduce\(%\S+ init: %\S+\).*?: \(tensor<" + dims + "x",
        text))


@pytest.mark.parametrize("num_bins", [63, 255])
def test_routing_lowers_to_one_reduction_and_the_fetch_s_gather(num_bins):
    """BW 2 and 8: one reduction over the ``[W, n]`` work (the row's code)
    where the formula it replaced had four, and no gather beside the feature
    rows' fetch (``gbdt_route_fetch_total{fetch=gather}``: the measured
    winner on the chip, PERF.md, PR 38): the category test is a select
    chain. Numeric-only it is the same program less the chain."""
    *args, is_cat = map(jnp.asarray, _case(num_bins, seed=1))
    W, n = args[2].shape[0], args[1].shape[0]
    for derived in (False, True):
        numeric = _lowered(lambda *a: _route_rows_to_children(
            *a, None, sibling_derived=derived), *args)
        categorical = _lowered(lambda *a: _route_rows_to_children(
            *a, is_cat, sibling_derived=derived), *args)
        for text in (numeric, categorical):
            assert _gathers(text) == 1
            assert _reduces_over(text, (W, n)) == 1
    old = _lowered(lambda *a: _reference_route(*a, is_cat), *args)
    assert _gathers(old) == 1 and _reduces_over(old, (W, n)) == 4


def test_wide_bitset_keeps_the_gather():
    """Above ``_ROUTE_SELECT_MAX_WORDS`` the bitset's word is gathered: the
    one bitset gather beside the fetch's."""
    *args, is_cat = map(jnp.asarray, _case(_BOUND_BINS + 1, seed=2))
    assert _gathers(_lowered(_route_rows_to_children, *args, is_cat)) == 2


def _cat_table(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    X = np.column_stack([rng.integers(0, 40, n), rng.normal(size=n),
                         rng.integers(0, 7, n), rng.normal(size=n)]
                        ).astype(np.float32)
    y = ((np.isin(X[:, 0], [1, 5, 9, 33, 38]) ^ (X[:, 2] == 3))
         ^ (rng.uniform(size=n) < 0.05)).astype(np.float32)
    return X, y


@pytest.mark.parametrize("policy", ["leafwise", "depthwise"])
def test_fit_counts_equal_predict_path_counts(policy):
    """Tier-1 twin of the benchmark's ``count_gap``: each leaf's ``node_cnt``
    is the number of rows the predict walk sends there. Growth routes with
    the select chain; the walk is checked twice, ``predict_tree_binned``
    (the node-slot walk, the chain again) and the gather walk with
    ``bit_test`` that it replaced."""
    X, y = _cat_table()
    ds = LightGBMDataset.construct(X, y, max_bin=63,
                                   categorical_features=(0, 2))
    before = _counted()
    b = train_booster(dataset=ds, num_iterations=3, objective="binary",
                      cfg=GrowConfig(num_leaves=9, min_data_in_leaf=5,
                                     growth_policy=policy, leaf_batch=3))
    # counted once where a leafwise program is built, once a level depthwise
    took = _counted_since(before)
    assert took["lookup", "gather"] == 0
    fetches = took["fetch", _FETCH]
    assert took["lookup", "select"] == fetches
    assert fetches == 1 if policy == "leafwise" else fetches > 1

    binned = jnp.asarray(np.asarray(ds.Xbt_d)[:, :ds.n].T)
    is_cat = jnp.asarray(ds.binner.is_cat_mask())
    M = b.trees.feat.shape[1]
    used_cat = False
    for t in range(b.trees.feat.shape[0]):
        tree = Tree(*(jnp.asarray(a[t]) for a in b.trees))
        ids = tree._replace(leaf_value=jnp.arange(M, dtype=jnp.float32))
        leaf = np.asarray(predict_tree_binned(ids, binned.T,
                                              is_cat=is_cat)).astype(int)
        np.testing.assert_array_equal(leaf, np.asarray(walk_by_gathers(
            ids, binned.astype(jnp.int32), b.depth_cap, is_cat)).astype(int))
        walked = np.bincount(leaf, minlength=M)
        leaves = np.flatnonzero(np.asarray(tree.is_leaf)
                                & (np.arange(M) < int(tree.node_count)))
        assert len(leaves) > 2
        np.testing.assert_array_equal(
            np.asarray(tree.node_cnt)[leaves].astype(int), walked[leaves])
        assert walked.sum() == walked[leaves].sum() == ds.n
        used_cat |= bool(np.asarray(tree.cat_bitset).any())
    assert used_cat, "no categorical split was taken"


def _fit_trees(cfg, categorical, seed=11):
    """Three trees of a booster on a small table, as arrays."""
    X, y = _cat_table(n=2500, seed=seed)
    ds = LightGBMDataset.construct(
        X, y, max_bin=63, categorical_features=(0, 2) if categorical else ())
    b = train_booster(dataset=ds, num_iterations=3, objective="binary",
                      cfg=GrowConfig(**dict(dict(num_leaves=11,
                                                 min_data_in_leaf=5), **cfg)))
    return [np.asarray(a) for a in b.trees]


def _grow_sharded(shards=4, n=4096, F=6, B=32):
    """``grow_tree`` a shard a host device inside ``shard_map``, as the
    four-chip cell runs it; -> the tree's arrays."""
    rng = np.random.default_rng(5)
    binned = rng.integers(0, B, (F, n)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    cfg = GrowConfig(num_leaves=15, num_bins=B, min_data_in_leaf=5,
                     quantized_grad=True, leaf_batch=4,
                     quant_renew_leaf=False)
    is_cat = jnp.asarray([False] * (F - 2) + [True] * 2)

    def grow(b, g, h, v, fm, k):
        return growth.grow_tree(b, g, h, v, fm, cfg, "data", is_cat, k)[0]

    mesh = meshlib.make_mesh(devices=jax.devices()[:shards])
    fn = shard_map(grow, mesh=mesh,
                   in_specs=(pspec(None, "data"),) + (pspec("data"),) * 3
                   + (pspec(), pspec()), out_specs=pspec(), check_vma=False)
    tree = jax.jit(fn)(jnp.asarray(binned), jnp.asarray(grad), jnp.ones(n),
                       jnp.ones(n), jnp.ones(F, bool), jax.random.PRNGKey(0))
    return [np.asarray(a) for a in tree]


_FITS = {
    "numeric_only": lambda: _fit_trees(dict(leaf_batch=4), False),
    "leafwise_int8": lambda: _fit_trees(
        dict(leaf_batch=4, quantized_grad=True, quant_renew_leaf=False),
        True),
    "float": lambda: _fit_trees(dict(leaf_batch=4), True),
    "depthwise": lambda: _fit_trees(dict(growth_policy="depthwise"), True),
    "hist_blocks": lambda: _fit_trees(
        dict(leaf_batch=4, quantized_grad=True, hist_blocks=8), True),
    "shard_map_4_devices": _grow_sharded,
}


@pytest.mark.parametrize("grow", list(_FITS.values()), ids=list(_FITS))
def test_fits_grow_the_trees_of_the_formula_it_replaced(grow, monkeypatch):
    """The rewrite is exact: whole fits grow, to the bit, the trees they grow
    with the ``[W, n]`` formula patched back in."""
    # train_booster keeps its programs by configuration: each side builds its
    # own, and the patched one is not left behind for a later test
    monkeypatch.setattr(booster_mod, "_STEP_CACHE",
                        type(booster_mod._STEP_CACHE)())
    got = grow()
    booster_mod._STEP_CACHE.clear()
    monkeypatch.setattr(growth, "_route_rows_to_children",
                        jax.named_scope("gbdt_route")(_reference_route))
    want = grow()
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert any(np.asarray(g).any() for g in got)
