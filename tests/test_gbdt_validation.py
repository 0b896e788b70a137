"""The validated fit (PR 35): an exact AUC on the device, a validation set
built once against the training set's binner, a scorer without per-row
gathers, and an early-stopping harness that stages the round once."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _gbdt_reference import auc_float64, es_scan_two_stage, walk_by_gathers
from mmlspark_tpu.models.gbdt import booster as gb
from mmlspark_tpu.models.gbdt.growth import (GrowConfig, Tree, bitset_words,
                                             predict_tree_binned)
from mmlspark_tpu.models.gbdt.objectives import (auc_device, eval_metric,
                                                 get_objective)
from mmlspark_tpu.parallel import mesh as meshlib
from mmlspark_tpu.parallel.compat import shard_map
from mmlspark_tpu.parallel.placement import pspec as P

# -- the metric ---------------------------------------------------------------


def _margin(ties: str, n: int, rng):
    if ties == "constant":
        return np.full(n, 0.25, np.float32)
    if ties == "two_values":
        return rng.choice(np.float32([-1.5, 0.75]), size=n)
    if ties == "leaf_sums":         # what two small trees leave: most rows tie
        return (rng.choice(rng.normal(size=7), size=n)
                + rng.choice(rng.normal(size=5), size=n)).astype(np.float32)
    return rng.permutation(n).astype(np.float32) / n - 0.5     # all distinct


def _auc_on_mesh(shards: int, s, y, w):
    if shards == 1:
        return float(jax.jit(auc_device)(s, y, w))
    mesh = meshlib.make_mesh(devices=jax.devices()[:shards])
    fn = jax.jit(shard_map(
        lambda a, b, c: auc_device(a, b, c, axis_name="data")[None],
        mesh=mesh, in_specs=(P("data"),) * 3, out_specs=P("data"),
        check_vma=False))
    out = np.asarray(fn(s, y, w))
    assert np.all(out == out[0]), "shards disagree on a replicated metric"
    return float(out[0])


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("weights", ["unit", "random"])
@pytest.mark.parametrize("ties", ["constant", "two_values", "leaf_sums",
                                  "distinct"])
def test_device_auc_is_the_float64_rank_statistic(ties, weights, shards):
    # a literal a case: ``hash`` of a string is salted per process, and the
    # draw decided whether a case met its bound
    rng = np.random.default_rng(
        [{"constant": 1, "two_values": 2, "leaf_sums": 3, "distinct": 4}[ties],
         weights == "random"])
    n = 4096 + 8 * 37                   # not a multiple of the sum's block
    s = _margin(ties, n, rng)
    y = (rng.uniform(size=n) < 0.3 + 0.2 * np.tanh(s)).astype(np.float32)
    w = (np.ones(n, np.float32) if weights == "unit"
         else rng.uniform(0.1, 3.0, size=n).astype(np.float32))
    w[rng.uniform(size=n) < 0.05] = 0.0          # padding and dead rows
    got = _auc_on_mesh(shards, s, y, w)
    want = auc_float64(s, y, w)
    if ties == "constant":
        assert want == 0.5
    # unit weights: the sums are whole numbers, exact in f32, and what is left
    # is the result's own rounding: the metric is an f32 in [0.5, 1) (or its
    # mirror image under 0.5), where an ulp is 6e-8. Two ulps, 1.2e-7; the
    # benchmark's held-out day reads up to 1.57e-7 on sound runs (PERF.md,
    # PR 35) and its control, the margin carried in bfloat16, 7e-4 and up:
    # four orders over either bound.
    assert abs(got - want) < (1.2e-7 if weights == "unit" else 1e-6)


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("absent", ["positives", "negatives"])
def test_device_auc_with_one_class_absent_is_a_half(absent, shards):
    rng = np.random.default_rng(7)
    s = rng.normal(size=2048).astype(np.float32)
    y = np.full(2048, 0.0 if absent == "positives" else 1.0, np.float32)
    assert _auc_on_mesh(shards, s, y, np.ones(2048, np.float32)) == 0.5


def test_device_auc_at_a_held_out_days_size_loses_nothing_at_1e6():
    """2.95 M rows whose margin has the few hundred distinct values that two
    31-leaf trees leave, 3.4% positives: the blocked f32 sums against
    float64."""
    rng = np.random.default_rng(35)
    n = 2_949_120
    s = (rng.choice(rng.normal(size=31), size=n)
         + rng.choice(rng.normal(size=31), size=n)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.034 * (1 + np.tanh(s))).astype(np.float32)
    got = float(jax.jit(auc_device)(s, y, np.ones(n, np.float32)))
    assert abs(got - auc_float64(s, y)) < 2e-7


def test_eval_metric_returns_the_device_auc_for_the_override():
    rng = np.random.default_rng(3)
    s = np.round(rng.normal(size=700), 1).astype(np.float32)
    y = (s + rng.normal(size=700) > 0).astype(np.float32)
    w = rng.uniform(0.1, 2.0, size=700).astype(np.float32)
    name, value = eval_metric(get_objective("binary"), jnp.asarray(s),
                              jnp.asarray(y), jnp.asarray(w), metric="auc")
    assert name == "auc"
    assert abs(float(value) - auc_float64(s, y, w)) < 1e-6


# -- the fit ------------------------------------------------------------------


def _table(n=5000, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    X[:, 5] = rng.integers(0, 40, size=n)
    y = ((X[:, 0] + 0.5 * X[:, 1] + (X[:, 5] % 3 == 0)
          + rng.normal(scale=0.5, size=n)) > 0.5).astype(np.float32)
    return X, y


_FIT = dict(objective="binary", num_iterations=12,
            cfg=GrowConfig(num_leaves=15), early_stopping_rounds=4,
            eval_metric_name="auc")


@pytest.mark.parametrize("shards", [1, 4])
def test_auc_fit_is_fused_and_equals_the_host_loop(shards, monkeypatch):
    """``metric="auc"`` runs in the one fused program, on one device and
    under a ``data`` mesh, and the host loop, which reads the same device
    metric a round, records the same history, best iteration and model."""
    from mmlspark_tpu.observability import spans
    X, y = _table()
    mesh = meshlib.make_mesh(devices=jax.devices()[:shards])
    kw = dict(_FIT, valid_set=(X[:1200], y[:1200], None), max_bin=63,
              categorical_features=(5,), mesh=mesh)
    spans.clear_trace()
    fused = gb.train_booster(X[1200:], y[1200:], **kw)
    fit, = [e for e in spans.get_trace_events()
            if e["ph"] == "X" and e["name"] == "gbdt_fit"]
    assert fit["args"]["path"] == "fused_valid"
    monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_VALID", "1")
    host = gb.train_booster(X[1200:], y[1200:], **kw)
    assert list(fused.eval_history) == ["auc"]
    np.testing.assert_array_equal(
        np.float32(fused.eval_history["auc"]),
        np.float32(host.eval_history["auc"]))
    assert fused.best_iteration == host.best_iteration >= 0
    assert fused.model_string() == host.model_string()
    # and the recorded metric is the exact statistic of the held-out rows
    # under the ensemble so far
    full = gb.train_booster(X[1200:], y[1200:],
                            **dict(kw, early_stopping_rounds=0))
    for it in (0, len(full.eval_history["auc"]) - 1):
        margin = full.predict_raw(X[:1200], num_iteration=it + 1)[:, 0]
        assert abs(full.eval_history["auc"][it]
                   - auc_float64(margin, y[:1200])) < 1e-6


def test_a_validation_dataset_built_once_equals_the_arrays():
    """``construct(reference=train)`` bins with the training set's binner
    into its storage dtype; fits against it equal fits given the arrays."""
    X, y = _table(seed=1)
    w = np.random.default_rng(2).uniform(0.5, 2.0, size=1001).astype(
        np.float32)
    train = gb.LightGBMDataset.construct(X[1001:], y[1001:], max_bin=63,
                                         categorical_features=(5,),
                                         bin_dtype="uint8")
    held = gb.LightGBMDataset.construct(X[:1001], y[:1001], w,
                                        reference=train)
    assert held.binner is train.binner and held.mesh is train.mesh
    assert held.Xbt_d.dtype == train.Xbt_d.dtype == jnp.uint8
    assert held.Xbt_d.shape[0] == 6 and held.n == 1001
    np.testing.assert_array_equal(
        np.asarray(held.Xbt_d)[:, :1001].T,
        train.binner.transform(X[:1001]).astype(np.uint8))
    a = gb.train_booster(dataset=train, valid_set=held, **_FIT)
    b = gb.train_booster(dataset=train, valid_set=held, **_FIT)   # held again
    c = gb.train_booster(dataset=train, valid_set=(X[:1001], y[:1001], w),
                         **_FIT)
    assert a.model_string() == b.model_string() == c.model_string()
    assert a.eval_history == b.eval_history == c.eval_history
    assert a.best_iteration == c.best_iteration


def test_a_validation_dataset_of_another_binner_is_refused():
    X, y = _table(seed=2)
    train = gb.LightGBMDataset.construct(X[1000:], y[1000:], max_bin=63)
    other = gb.LightGBMDataset.construct(X[:1000], y[:1000], max_bin=63)
    with pytest.raises(ValueError, match="reference="):
        gb.train_booster(dataset=train, valid_set=other, **_FIT)
    with pytest.raises(ValueError, match="features"):
        gb.LightGBMDataset.construct(X[:1000, :4], y[:1000], reference=train)


# -- the scorer ---------------------------------------------------------------


def _random_tree(depth: int, leaves: int, F: int, B: int, cat_feats, rng):
    """A tree of ``leaves`` leaves whose deepest leaf is ``depth`` splits
    down, slots handed out as the growers do (children above their parent),
    splits numeric or categorical at random."""
    M, BW = 2 * leaves - 1, bitset_words(B)
    t = dict(feat=np.zeros(M, np.int32), thr_bin=np.zeros(M, np.int32),
             left=np.zeros(M, np.int32), right=np.zeros(M, np.int32),
             is_leaf=np.ones(M, bool), cat_bitset=np.zeros((M, BW), np.uint32))
    at_depth, used, spine = {0: 0}, 1, 0
    for split in range(leaves - 1):
        if split < depth:                  # the spine reaches the depth asked
            j, spine = spine, None
        else:
            open_ = [k for k, d in at_depth.items()
                     if t["is_leaf"][k] and d < depth]
            if not open_:
                break
            j = int(rng.choice(open_))
        f = int(rng.integers(F))
        t["feat"][j], t["is_leaf"][j] = f, False
        t["left"][j], t["right"][j] = used, used + 1
        if f in cat_feats:
            member = rng.uniform(size=BW * 32) < 0.5
            t["cat_bitset"][j] = np.packbits(
                member.reshape(BW, 32), axis=1, bitorder="little").view(
                    np.uint32)[:, 0]
        else:
            t["thr_bin"][j] = int(rng.integers(B - 1))
        at_depth[used] = at_depth[used + 1] = at_depth[j] + 1
        if spine is None:
            spine = used + int(rng.integers(2))
        used += 2
    zeros = np.zeros(M, np.float32)
    return Tree(leaf_value=jnp.arange(M, dtype=jnp.float32) + 1.0,
                node_count=jnp.int32(used), node_grad=zeros, node_hess=zeros,
                node_cnt=zeros, split_gain=zeros, node_value=zeros,
                **{k: jnp.asarray(v) for k, v in t.items()}), max(
                    at_depth.values())


@pytest.mark.parametrize("depth,bins", [(1, 63), (2, 255), (5, 255), (10, 255),
                                        (17, 40), (30, 255)])
def test_scorer_lands_every_row_on_the_leaf_the_gather_walk_finds(depth,
                                                                  bins):
    rng = np.random.default_rng(100 + depth)
    F, n, cat_feats = 9, 3000, (2, 5, 6)
    tree, reached = _random_tree(depth, 31, F, bins, cat_feats, rng)
    assert reached == depth
    binned = rng.integers(0, bins, size=(n, F)).astype(np.uint8)
    is_cat = jnp.zeros(F, bool).at[jnp.asarray(cat_feats)].set(True)
    want = np.asarray(walk_by_gathers(tree, jnp.asarray(binned, jnp.int32),
                                      30, is_cat))
    got = np.asarray(jax.jit(lambda t, b: predict_tree_binned(
        t, b, is_cat=is_cat))(tree, jnp.asarray(binned.T)))
    np.testing.assert_array_equal(got, want)     # leaf values are slot ids
    assert len(np.unique(got)) > 1 or depth == 1
    # numeric-only trees take the path without bitsets
    numeric = tree._replace(cat_bitset=jnp.zeros_like(tree.cat_bitset))
    np.testing.assert_array_equal(
        np.asarray(predict_tree_binned(numeric, jnp.asarray(binned.T))),
        np.asarray(walk_by_gathers(numeric, jnp.asarray(binned, jnp.int32),
                                   30)))


def test_scorer_lowers_to_no_gather_over_the_rows():
    rng = np.random.default_rng(5)
    tree, _ = _random_tree(6, 31, 9, 255, (2, 5), rng)
    is_cat = jnp.zeros(9, bool).at[jnp.asarray((2, 5))].set(True)
    text = jax.jit(lambda t, b: predict_tree_binned(
        t, b, is_cat=is_cat)).lower(
            tree, jnp.zeros((9, 4096), jnp.uint8)).as_text()
    assert "gbdt_valid_score" in jax.jit(lambda t, b: predict_tree_binned(
        t, b, is_cat=is_cat)).lower(
            tree, jnp.zeros((9, 4096), jnp.uint8)).as_text(debug_info=True)
    gathers = [line for line in text.splitlines() if "gather" in line
               and "4096" in line]
    assert not gathers, gathers


# -- the harness --------------------------------------------------------------


@pytest.mark.parametrize("higher,rounds,tol", [(True, 2, 0.0), (False, 3, 0.0),
                                               (True, 0, 0.0),
                                               (False, 2, 0.05)])
def test_es_scan_stages_the_round_once_with_the_two_stage_outputs(
        higher, rounds, tol):
    curve = jnp.asarray([0.5, 0.6, 0.7, 0.65, 0.69, 0.71, 0.6, 0.6, 0.6, 0.6],
                        jnp.float32)
    calls = []

    def one_iter(it, state):
        calls.append(it)
        acc, key = state
        m = curve[it] if higher else 1.0 - curve[it]
        packed = jnp.stack([it.astype(jnp.float32), acc, m])
        return (acc + m, key + 1), packed, m

    state0 = (jnp.float32(0.0), jnp.int32(3))
    got = jax.jit(lambda s: gb._fused_es_scan(
        one_iter, s, 10, rounds, higher, True, tol=tol))(state0)
    assert len(calls) == 1, "one_iter was traced more than once"
    want = jax.jit(lambda s: es_scan_two_stage(
        one_iter, s, 10, rounds, higher, tol=tol))(state0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if rounds == 2 and higher:
        assert (int(got[2]), int(got[3])) == (5, 2)       # stopped early
