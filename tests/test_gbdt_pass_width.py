"""A leafwise round's histogram pass, as wide as the round's frontier
(``growth._pass_widths``, ``growth._hist_at_width``; PERF.md, PR 34).

A pass over ``W2 = 2 * leaf_batch`` child slots costs the MXU its operand's
height, and the first rounds of a tree hold 2, 4 and 8 children. The round's
pass is staged at a few static widths and runs the narrowest that holds its
live children; the slots past them were zeros and are zeros. So the trees
have to be the ones the single width ``W2`` grows, which was the program
before: to the bit, for int8 and for float statistics, on one shard and on
four, under ``hist_blocks`` and ``voting_parallel``.

With int8 statistics a round's pass holds one child of every split and the
sibling is its parent's int32 sums less that child's
(``growth._sibling_is_derived``, ``growth._derive_siblings``; PERF.md, PR 36):
``leaf_batch`` node slots, not ``2 * leaf_batch``, and again the same trees
to the bit, now against the build that sums both children. Float statistics
keep the program they had.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.gbdt import growth
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.ops.histogram import node_histogram, node_histogram_sums
from mmlspark_tpu.parallel import mesh as meshlib
from mmlspark_tpu.parallel.compat import shard_map
from mmlspark_tpu.parallel.placement import pspec

# -- the rule -----------------------------------------------------------------


@pytest.mark.parametrize("B,stats,leaf_batch,widths", [
    # no width under 4: measured, a pass is flat up to 4 nodes (at 255 bins
    # int8, 2 and 4 nodes share the root's 32-row operand anyway)
    (255, "int8", 1, (2,)),
    (255, "int8", 2, (4,)),
    (255, "int8", 4, (4, 8)),
    (255, "int8", 8, (4, 8, 16)),
    (255, "int8", 10, (4, 8, 16, 20)),
    # the plain layout takes over at 96 rows too (W > 21), with two tiles a
    # feature: as high as the folded 16, and not the same kernel
    (255, "int8", 15, (4, 8, 16, 30)),
    # bf16 packs two rows a word: other heights, the same widths
    (255, "bf16", 1, (2,)),
    (255, "bf16", 2, (4,)),
    (255, "bf16", 4, (4, 8)),
    (255, "bf16", 8, (4, 8, 16)),
    (255, "bf16", 10, (4, 8, 16, 20)),
    # the plain layout pads the stats to 16 rows: 10 nodes are as high as 8
    (63, "int8", 1, (2,)),
    (63, "int8", 2, (4,)),
    (63, "int8", 4, (4, 8)),
    (63, "int8", 5, (4, 10)),
    (63, "int8", 8, (4, 8, 16)),
    (63, "int8", 10, (4, 8, 16, 20)),
    (63, "bf16", 8, (4, 8, 16)),
])
def test_the_widths_are_a_rule_of_static_shapes(B, stats, leaf_batch, widths):
    got = growth._pass_widths(2 * leaf_batch, B, stats == "int8")
    assert got == widths
    assert got[-1] == 2 * leaf_batch and list(got) == sorted(set(got))


@pytest.mark.parametrize("B", [255, 63])
@pytest.mark.parametrize("leaf_batch,widths", [
    (1, (1,)), (2, (2,)), (4, (4,)), (5, (5,)), (8, (4, 8)), (10, (4, 10)),
    (15, (4, 8, 15))])
def test_a_derived_round_is_staged_at_leaf_batch_slots(B, leaf_batch, widths):
    """int8 statistics: the pass holds the left children alone, so the rule
    is asked for ``leaf_batch`` slots; the same rule, no new one."""
    assert growth._pass_widths(leaf_batch, B, True) == widths


# -- one pass, every count of live positions ----------------------------------


@pytest.mark.parametrize("stats", ["int8", "float"])
@pytest.mark.parametrize("live", [1, 2, 3, 4, 5, 8, 9, 14, 16])
def test_a_pass_at_the_live_width_is_the_full_width_pass(live, stats):
    """Rows sit at positions under ``live`` or at none: the histogram of the
    narrowest width that holds them, padded, is the 16-wide one, every bit."""
    rng = np.random.default_rng(live)
    n, F, B, W = 4096, 5, 63, 16
    binned = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    pos = jnp.asarray(rng.integers(-1, live, n), jnp.int32)
    base = rng.normal(size=(3, n)).astype(np.float32)
    scales = None
    if stats == "int8":
        base, scales = np.round(base * 40).astype(np.int8), jnp.ones(3)
    base = jnp.asarray(base)

    def hist_of(w):
        return node_histogram(binned, pos, base, w, B, scales=scales)

    got, which = jax.jit(lambda k: growth._hist_at_width(
        hist_of, W, k, B, stats == "int8"))(jnp.int32(live))
    assert growth._pass_widths(W, B, stats == "int8")[int(which)] == min(
        w for w in (4, 8, 16) if w >= live)
    want = hist_of(W)
    assert got.shape == want.shape == (F, 3 * W, B)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert not np.asarray(got)[:, 3 * live:].any()
    assert np.asarray(got)[:, :3 * live].any()


@pytest.mark.parametrize("live", range(1, 9))
@pytest.mark.parametrize("blocks", [0, 2], ids=["plain", "blocks2"])
def test_derived_children_are_the_two_children_pass_s(live, blocks):
    """``live`` of 8 candidates split. The pass over their left children at
    the narrowest width that holds them, the right ones taken from the
    parents' sums: the ``[F, 48, B]`` int32 array of the pass over all 16
    children, every bit; a candidate that does not split leaves zeros."""
    rng = np.random.default_rng(live)
    n, F, B, KB = 4096, 5, 63, 8
    binned = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    base = jnp.asarray(rng.integers(-127, 128, (3, n)), jnp.int8)
    cand = rng.integers(-1, KB, n)                # -1: outside the frontier
    goes_left = rng.uniform(size=n) < 0.4
    do = jnp.arange(KB) < live
    splits = (cand >= 0) & (cand < live)
    both = np.where(splits, 2 * cand + ~goes_left, -1)
    lefts = np.where(splits & goes_left, cand, -1)

    def sums(pos, w):
        pos = jnp.asarray(pos, jnp.int32)
        if not blocks:
            return node_histogram_sums(binned, pos, base, w, B,
                                       quantized=True)
        return growth._block_node_hists(binned, pos, base, w, B, None, blocks,
                                        n // blocks, sums=True)

    slots = jnp.asarray(rng.permutation(2 * KB + 3)[:KB])   # in some cache
    hsum = jnp.zeros((blocks,) * bool(blocks) + (F, 2 * KB + 3, 3, B),
                     jnp.int32).at[..., slots, :, :].set(
        sums(cand, KB).reshape((blocks,) * bool(blocks) + (F, KB, 3, B)))
    left, _ = jax.jit(lambda k: growth._hist_at_width(
        lambda w: sums(lefts, w), KB, k, B, True))(jnp.int32(live))
    got = growth._derive_siblings(left, hsum, slots, do)
    want = sums(both, 2 * KB)
    got = got.reshape(want.shape)
    assert got.dtype == want.dtype == jnp.int32 and got.shape == want.shape
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert np.asarray(got)[..., 3 * 2 * live - 3:3 * 2 * live, :].any()
    assert not np.asarray(got)[..., 3 * 2 * live:, :].any()


# -- trees --------------------------------------------------------------------

N, F, B = 4096, 6, 63


def _rows(seed=0):
    """Two numeric steps, an interaction and one category subset, so that a
    tree of 15 leaves has real splits to find in every round."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, B, (F, N))
    X[5] = rng.integers(0, 12, N)
    logit = (2.0 * (X[0] > 20) - 1.5 * (X[1] < 30) * (X[2] > 40)
             + 1.2 * np.isin(X[5], [1, 4, 7]) + 0.8 * (X[3] > 50))
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(0.5 - logit))).astype(
        np.float32)
    p = 0.5
    return X.astype(np.uint8), (p - y).astype(np.float32), np.full(
        N, p * (1 - p), np.float32)


def _grow(widths, *, stats="int8", shards=1, cat=False, rows=None,
          derive=True, **cfg):
    """One tree as numpy arrays; ``widths`` None is the rule as shipped, a
    tuple stands where ``growth._pass_widths`` stands; ``derive`` False
    sums both children of every split whatever the statistics."""
    binned, grad, hess = rows or _rows()
    cfg = GrowConfig(**dict(dict(
        num_leaves=15, num_bins=B, min_data_in_leaf=5, leaf_batch=4,
        quantized_grad=stats == "int8", quant_renew_leaf=False), **cfg))
    is_cat = jnp.asarray([False] * (F - 1) + [True]) if cat else None
    axis = "data" if shards > 1 else None

    def fn(b, g, h, v, fm, key):
        return growth.grow_tree(b, g, h, v, fm, cfg, axis, is_cat, key)[0]

    if shards > 1:
        fn = shard_map(fn, mesh=meshlib.make_mesh(
            devices=jax.devices()[:shards]),
            in_specs=(pspec(None, "data"),) + (pspec("data"),) * 3
            + (pspec(), pspec()), out_specs=pspec(), check_vma=False)
    real = growth._pass_widths, growth._sibling_is_derived
    if widths is not None:
        growth._pass_widths = lambda W, B_, q: widths
    if not derive:
        growth._sibling_is_derived = lambda quantized: False
    try:
        tree = jax.jit(fn)(jnp.asarray(binned), jnp.asarray(grad),
                           jnp.asarray(hess), jnp.ones(N), jnp.ones(F, bool),
                           jax.random.PRNGKey(0))
    finally:
        growth._pass_widths, growth._sibling_is_derived = real
    return jax.tree_util.tree_map(np.asarray, tree)


def _same_tree(a, b):
    for name, x, y in zip(a._fields, a, b):
        assert x.tobytes() == y.tobytes(), name


@pytest.mark.parametrize("cat", [False, True], ids=["numeric", "categorical"])
@pytest.mark.parametrize("stats", ["int8", "float"])
@pytest.mark.parametrize("shards,cfg", [
    (1, {}), (4, {}), (1, dict(hist_blocks=4)), (4, dict(hist_blocks=4)),
    (4, dict(voting=True, top_k=2)),
], ids=["1", "4", "1-blocks4", "4-blocks4", "4-voting"])
def test_trees_are_the_single_width_s_to_the_bit(shards, cfg, stats, cat):
    """The rule monkeypatched to the one width ``W2``, both children
    summed, stages the program PR 34's parent staged."""
    ours = _grow(None, stats=stats, shards=shards, cat=cat, **cfg)
    parents = _grow((8,), stats=stats, shards=shards, cat=cat, derive=False,
                    **cfg)
    assert int(ours.node_count) >= 2 * 8 - 1        # rounds of 1, 2, 4 splits
    _same_tree(ours, parents)


@pytest.mark.parametrize("cat", [False, True], ids=["numeric", "categorical"])
@pytest.mark.parametrize("leaf_batch", [1, 5, 8])
@pytest.mark.parametrize("shards,cfg", [
    (1, {}), (4, {}), (1, dict(hist_blocks=4)), (4, dict(hist_blocks=4)),
    (4, dict(voting=True, top_k=2)),
], ids=["1", "4", "1-blocks4", "4-blocks4", "4-voting"])
def test_int8_trees_are_the_two_children_build_s_to_the_bit(shards, cfg,
                                                            leaf_batch, cat):
    """The same build with the derivation held off sums both children of
    every split at PR 34's widths, which is this PR's parent. The sibling is
    derived from this shard's (this block's) int32 sums before the scale and
    before anything crosses shards, so the f32 array that is reduced, voted
    on and searched is the one it was."""
    ours = _grow(None, shards=shards, cat=cat, leaf_batch=leaf_batch,
                 num_leaves=21, **cfg)
    parents = _grow(None, shards=shards, cat=cat, leaf_batch=leaf_batch,
                    num_leaves=21, derive=False, **cfg)
    assert int(ours.node_count) >= 2 * 12 - 1
    _same_tree(ours, parents)


@pytest.mark.parametrize("stats", ["int8", "float"])
def test_a_tree_that_ends_in_a_round_of_one_live_split(stats):
    """Feature 0 splits the rows, feature 1 splits the left half again and
    nothing else is worth ``min_gain_to_split``: the second round has two
    candidates and one split, the third none. The parent's build: one width,
    both children summed."""
    rng = np.random.default_rng(3)
    X = rng.integers(0, B, (F, N))
    left = X[0] <= 30
    y = np.where(left, np.where(X[1] <= 30, 0.9, 0.6), 0.1)
    rows = (X.astype(np.uint8), (0.5 - y).astype(np.float32),
            np.full(N, 0.25, np.float32))
    ours = _grow(None, stats=stats, rows=rows, min_gain_to_split=5.0)
    parents = _grow((8,), stats=stats, rows=rows, min_gain_to_split=5.0,
                    derive=False)
    assert int(ours.node_count) == 5
    assert sorted(ours.feat[~ours.is_leaf]) == [0, 1]
    _same_tree(ours, parents)


# -- the program --------------------------------------------------------------


def _lower_args(n=2048):
    return (jnp.zeros((F, n), jnp.uint8), jnp.zeros(n), jnp.ones(n),
            jnp.ones(n), jnp.ones(F, bool), jax.random.PRNGKey(0))


def _grow_fn(**cfg):
    cfg = GrowConfig(**dict(dict(num_leaves=31, num_bins=255,
                                 quantized_grad=True,
                                 quant_renew_leaf=False), **cfg))
    return lambda b, g, h, v, fm, k: growth.grow_tree(b, g, h, v, fm, cfg,
                                                      None, None, k)[0]


def _kernel_calls(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernel_calls(sub)
    return n


def _switches(jaxpr, found):
    """Every ``cond`` all of whose branches call the kernel."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            calls = [_kernel_calls(b.jaxpr) for b in eqn.params["branches"]]
            if all(calls):
                found.append(calls)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _switches(sub, found)
    return found


@pytest.mark.parametrize("stats,widths", [("int8", (4, 8)),
                                          ("float", (4, 8, 16))])
def test_a_round_holds_one_switch_of_one_kernel_call_a_branch(
        stats, widths, monkeypatch):
    """The cells' rounds (255 bins, ``leaf_batch`` 8) under the Pallas engine:
    the root's call, and one switch whose branches hold one call each (int8:
    at 4 and 8 slots, the left children's). The routing, the split search
    and the tree update are staged once."""
    monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "pallas")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
    jaxpr = jax.make_jaxpr(_grow_fn(quantized_grad=stats == "int8"))(
        *_lower_args()).jaxpr
    assert _switches(jaxpr, []) == [[1] * len(widths)]
    assert _kernel_calls(jaxpr) == 1 + len(widths)
    assert str(jaxpr).count("gbdt_route") == str(jax.make_jaxpr(_grow_fn(
        quantized_grad=stats == "int8", leaf_batch=1))(
            *_lower_args()).jaxpr).count("gbdt_route")


def _kernel_results(jaxpr, found):
    """The result shapes of every kernel call."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(tuple(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_results(sub, found)
    return found


@pytest.mark.parametrize("bins,leaf_batch", [(255, 8), (63, 8), (255, 5),
                                             (63, 5)])
def test_an_int8_round_stages_no_kernel_wider_than_leaf_batch(
        bins, leaf_batch, monkeypatch):
    """Under the Pallas engine the int8 build's kernels are the root's and
    those of ``_pass_widths(leaf_batch)`` node slots; the build that sums
    both children has a higher one (``2 * leaf_batch`` slots), which is the
    launch a device trace no longer shows."""
    monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "pallas")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
    binned, _, _, pos = _lower_args()[:4]

    def kernel_of(w):
        return _kernel_results(jax.make_jaxpr(
            lambda: node_histogram_sums(
                binned, pos.astype(jnp.int32), jnp.zeros((3, pos.size),
                                                         jnp.int8),
                w, bins, quantized=True))().jaxpr, [])[0]

    def staged():
        return _kernel_results(jax.make_jaxpr(_grow_fn(
            num_bins=bins, leaf_batch=leaf_batch))(*_lower_args()).jaxpr, [])

    derived = staged()
    assert derived == [kernel_of(1)] + [
        kernel_of(w) for w in growth._pass_widths(leaf_batch, bins, True)]
    monkeypatch.setattr(growth, "_sibling_is_derived", lambda q: False)
    rows = lambda shapes: max(s[-2] for s in shapes[1:])
    assert kernel_of(2 * leaf_batch) in staged()
    assert kernel_of(2 * leaf_batch) not in derived
    assert rows(derived) <= kernel_of(leaf_batch)[-2] <= rows(staged())


_PARENT_JAX = "0.9.0"      # the jax the parent's texts were hashed under


@pytest.mark.parametrize("cfg,sha", [
    (dict(), "1f0da95a76ee0a9f50181b8d65901857ccf2227284a0137127affc3f89262fa8"),
    (dict(leaf_batch=1),
     "608dc54b76e97eb407e2b29eeb249bf4cdc6319951bc230d918ee9a0a8fb558c"),
    (dict(leaf_batch=5, num_bins=B),
     "8d63264843d80721361572d676c73c73ab2de40ac340d15213145fa315fdd60a"),
], ids=["cells", "leaf_batch1", "63bins-leaf_batch5"])
def test_the_float_program_is_the_parent_s(cfg, sha, monkeypatch):
    """Float statistics sum both children, as they did: under any jax the
    lowered text of the float build is the text of the build with the
    derivation held off, and it never reaches ``_derive_siblings``. From PR
    36 to PR 37 it was also the text of PR 36's parent (873e312), byte for
    byte, by its hash under the jax it was lowered with.

    PR 38 changed the three texts by design, and re-pinned them: row routing
    (``growth._route_rows_to_children``) finds a row's candidate by one
    reduction over ``W`` where it made four, and returns the pass position
    the leafwise caller used to reduce itself. The hashes are the texts of
    that program; ``tests/test_route_rows.py`` holds float fits, tree for
    tree to the bit, to the routing it replaced."""
    def text():
        return re.sub(r"module @\S+", "module @m", jax.jit(_grow_fn(
            quantized_grad=False, **cfg)).lower(*_lower_args()).as_text(),
            count=1)

    monkeypatch.setattr(growth, "_derive_siblings", None)
    shipped = text()
    if jax.__version__ == _PARENT_JAX:
        assert hashlib.sha256(shipped.encode()).hexdigest() == sha
    monkeypatch.setattr(growth, "_sibling_is_derived", lambda q: False)
    assert text() == shipped


def test_leaf_batch_one_stages_no_switch(monkeypatch):
    """``W2 = 2`` has the one width: the text is the text without the
    mechanism."""
    def text():
        return jax.jit(_grow_fn(leaf_batch=1, num_bins=B)).lower(
            *_lower_args()).as_text()

    shipped = text()
    monkeypatch.setattr(growth, "_hist_at_width",
                        lambda hist_of, W, *a: (hist_of(W), 0))
    assert text() == shipped


def _run_tally(leaf_batch, stats):
    """``(widths, runs)`` of one 31-leaf tree on the toy rows: the layout's
    widths under the root and how often a pass ran at each."""
    cfg = GrowConfig(num_leaves=31, num_bins=255, min_data_in_leaf=5,
                     leaf_batch=leaf_batch, quantized_grad=stats == "int8",
                     quant_renew_leaf=False)
    binned, grad, hess = _rows()

    def fn(b, g, h, k):
        got = []
        tree, _ = growth.grow_tree(b, g, h, jnp.ones(N), jnp.ones(F, bool),
                                   cfg, None, None, k, run_tally=got)
        return tree.node_count, got[0]

    nodes, tally = jax.jit(fn)(jnp.asarray(binned), jnp.asarray(grad),
                               jnp.asarray(hess), jax.random.PRNGKey(0))
    assert int(nodes) == 61
    widths = growth.run_tally_layout(cfg)
    assert widths[0] == 1 and int(tally[1]) == 1            # the root's
    return widths[1:], [int(r) for r in tally[2:]]


@pytest.mark.parametrize("leaf_batch,stats,widths", [
    (8, "int8", (4, 8)), (8, "float", (4, 8, 16)),
    (1, "int8", (1,)), (1, "float", (2,))])
def test_a_run_counts_each_staged_width_it_ran_at(leaf_batch, stats, widths):
    """A 31-leaf tree's rounds split 1, 2, 4, 8, 8, 7 leaves at ``leaf_batch``
    8 and one leaf each at 1: every staged width runs, and the tally's
    entries are the staged widths (that staged is a superset of run is the
    lowered text's to hold, above)."""
    staged, runs = _run_tally(leaf_batch, stats)
    assert staged == widths
    assert runs == {(8, "int8"): [3, 3], (8, "float"): [2, 1, 3],
                    (1, "int8"): [30], (1, "float"): [30]}[leaf_batch, stats]


@pytest.mark.parametrize("leaf_batch", [1, 8])
@pytest.mark.parametrize("stats,per_split", [("int8", 1), ("float", 2)])
def test_a_fit_says_how_a_round_gets_its_siblings(stats, per_split,
                                                  leaf_batch):
    """On the fit's own span, by the live positions it tells: a round that
    derives its siblings (int8) holds its splits, one that sums both
    children (float) holds two a split; a root is one."""
    from mmlspark_tpu.models.gbdt.booster import train_booster
    from mmlspark_tpu.observability import spans

    binned, grad, _ = _rows()
    spans.clear_trace()
    booster = train_booster(
        binned.T.astype(np.float32), (grad < 0).astype(np.float32),
        objective="binary", num_iterations=2, max_bin=B,
        mesh=meshlib.make_mesh(devices=jax.devices()[:1]),
        cfg=GrowConfig(num_leaves=15, min_data_in_leaf=5,
                       leaf_batch=leaf_batch, quant_warmup_iters=0,
                       quantized_grad=stats == "int8",
                       quant_renew_leaf=False))
    fit, = [e["args"] for e in spans.get_trace_events()
            if e["name"] == "gbdt_fit"]
    splits = int((~booster.trees.is_leaf).sum())
    assert fit["live"] == 2 + splits * per_split
