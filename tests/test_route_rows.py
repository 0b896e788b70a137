"""Row routing of batched tree growth (``growth._route_rows_to_children``).

The category membership test is a select over the bitset's words up to
``growth._ROUTE_SELECT_MAX_WORDS`` and a gather above it. Both must give, for
every row, the bit a plain NumPy lookup ``bits[idx >> 5] >> (idx & 31) & 1``
gives; the lowering pins that the select needs no gather; the end-to-end pin
holds a fit's own row counts against the predict path (``bit_test``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _gbdt_reference import walk_by_gathers
from mmlspark_tpu.models.gbdt import growth
from mmlspark_tpu.models.gbdt.booster import LightGBMDataset, train_booster
from mmlspark_tpu.models.gbdt.growth import (GrowConfig, Tree,
                                             _route_rows_to_children,
                                             bitset_words,
                                             predict_tree_binned)
from mmlspark_tpu.observability import metrics

_BOUND_BINS = 32 * growth._ROUTE_SELECT_MAX_WORDS      # widest select chain


def _case(num_bins, seed, n=1500, F=9, W=6):
    """Random candidates: numeric and categorical features mixed, ``do``
    partly false, one inactive slot (-1, as a depthwise frontier pads)."""
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
              is_cat):
    idx = binned_t[feats].astype(np.int64)                          # [W, n]
    goleft = idx <= bins_[:, None]
    if is_cat is not None:
        word = bits_k[np.arange(len(feats))[:, None], idx >> 5]
        member = ((word >> (idx & 31).astype(np.uint32)) & 1).astype(bool)
        goleft = np.where(is_cat[feats][:, None], member, goleft)
    move = (row_node[None, :] == slots[:, None]) & do[:, None]
    new_row_node = row_node.copy()
    for w in range(len(feats)):
        new_row_node[move[w]] = np.where(goleft[w][move[w]], lid[w],
                                         lid[w] + 1)
    return new_row_node, move, goleft


def _lookups():
    return {k: metrics.counter("gbdt_route_lookup_total", lookup=k).value
            for k in ("select", "gather")}


def _lookups_since(before):
    return {k: v - before[k] for k, v in _lookups().items()}


@pytest.mark.parametrize(
    "num_bins", [31, 32, 33, 63, 64, 255, 256, _BOUND_BINS, _BOUND_BINS + 1])
def test_route_equals_numpy_bit_lookup(num_bins):
    case = _case(num_bins, seed=num_bins)
    before = _lookups()
    # a lambda of its own: every case traces, whatever shapes it shares
    got = jax.jit(lambda *a: _route_rows_to_children(*a))(
        *map(jnp.asarray, case))
    want = _np_route(*case)
    for name, g, w in zip(("new_row_node", "move", "goleft_k"), got, want):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)
    chain = num_bins <= _BOUND_BINS
    assert _lookups_since(before) == {"select": int(chain),
                                      "gather": int(not chain)}


def _parent_numeric_route(binned_t, row_node, slots, do, feats, bins_,
                          bits_k, lid):
    """The numeric-only routing as it stood before the select chain."""
    pos_oh = row_node[None, :] == slots[:, None]
    move = pos_oh & do[:, None]
    rows = binned_t[feats].astype(jnp.int32)
    goleft_k = rows <= bins_[:, None]
    in_any = jnp.any(move, axis=0)
    go_left_row = jnp.any(move & goleft_k, axis=0)
    lid_row = jnp.sum(jnp.where(move, lid[:, None], 0), axis=0)
    new_row_node = jnp.where(
        in_any, jnp.where(go_left_row, lid_row, lid_row + 1), row_node)
    return new_row_node, move, goleft_k


def _lowered(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return re.sub(r"module @\S+", "module @m", text, count=1)


def _gathers(text):
    return len(re.findall(r'stablehlo\.(?:dynamic_)?gather"?\(', text))


@pytest.mark.parametrize("num_bins", [63, 255])
def test_category_lookup_lowers_to_no_gather(num_bins):
    """BW 2 and 8: with ``is_cat`` set the routing has the feature-row fetch
    and no other gather; numeric-only it is the program it was."""
    *args, is_cat = map(jnp.asarray, _case(num_bins, seed=1))
    numeric = _lowered(
        lambda *a: _route_rows_to_children(*a, None), *args)
    categorical = _lowered(_route_rows_to_children, *args, is_cat)
    assert _gathers(numeric) == 1
    assert _gathers(categorical) == _gathers(numeric)
    assert numeric == _lowered(_parent_numeric_route, *args)


def test_wide_bitset_keeps_the_gather():
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
    before = _lookups()
    b = train_booster(dataset=ds, num_iterations=3, objective="binary",
                      cfg=GrowConfig(num_leaves=9, min_data_in_leaf=5,
                                     growth_policy=policy, leaf_batch=3))
    # counted once where a leafwise program is built, once a level depthwise
    took = _lookups_since(before)
    assert took["gather"] == 0
    assert took["select"] == 1 if policy == "leafwise" else took["select"] > 1

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
