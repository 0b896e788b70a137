"""What a fit ran, counted where it ran (``growth.run_tally_layout``;
PERF.md, PR 37).

Tree growth carries a few int32 scalars through its rounds: how many
histogram passes ran at each staged width and how many node positions of them
held rows. They leave the device in the transfer that brings the trees and
the fit tells them on its ``gbdt_fit`` span. Held here: the tally is the one a
plain replay of the round loop gives from the tree's own splits; every path
that downloads trees carries it; it is the one-device tally on a mesh; the
program is the program it was but for the tally; with telemetry off nothing is
recorded and the trees are the same.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.gbdt import booster as gb
from mmlspark_tpu.models.gbdt import growth
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.observability import metrics, spans
from mmlspark_tpu.parallel import mesh as meshlib
from mmlspark_tpu.parallel.compat import shard_map
from mmlspark_tpu.parallel.placement import pspec

N, F, B = 4096, 6, 63
TOLD = ("passes", "launches", "slots", "live", "iterations")


def _rows(shape="full", seed=0):
    """``(binned [F, N], grad, hess)``. ``full``: two numeric steps, an
    interaction and a category subset, splits to find in every round;
    ``two_splits``: feature 0 splits the rows, feature 1 the left half, and
    nothing else is worth a ``min_gain_to_split`` of 5."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, B, (F, N))
    if shape == "two_splits":
        y = np.where(X[0] <= 30, np.where(X[1] <= 30, 0.9, 0.6), 0.1)
    else:
        X[5] = rng.integers(0, 12, N)
        logit = (2.0 * (X[0] > 20) - 1.5 * (X[1] < 30) * (X[2] > 40)
                 + 1.2 * np.isin(X[5], [1, 4, 7]) + 0.8 * (X[3] > 50))
        y = (rng.uniform(size=N) < 1 / (1 + np.exp(0.5 - logit))).astype(
            np.float32)
    return (X.astype(np.uint8), (0.5 - y).astype(np.float32),
            np.full(N, 0.25, np.float32))


def _cfg(stats="int8", **kw):
    return GrowConfig(**dict(dict(
        num_leaves=31, num_bins=B, min_data_in_leaf=5, leaf_batch=8,
        quantized_grad=stats == "int8", quant_renew_leaf=False,
        quant_warmup_iters=0), **kw))


def _grown(cfg, rows=None, shards=1):
    """``(tree, tally)`` as numpy, of one tree grown under ``jax.jit``."""
    binned, grad, hess = rows or _rows()
    grow = (growth.grow_tree_depthwise if cfg.growth_policy == "depthwise"
            else growth.grow_tree)
    axis = "data" if shards > 1 or (
        isinstance(cfg.hist_blocks, int) and cfg.hist_blocks > 1) else None

    def fn(b, g, h, v, fm, key):
        got = []
        tree, _ = grow(b, g, h, v, fm, cfg, axis, None, key, run_tally=got)
        return tree, got[0]

    if axis:
        fn = shard_map(fn, mesh=meshlib.make_mesh(
            devices=jax.devices()[:shards]),
            in_specs=(pspec(None, "data"),) + (pspec("data"),) * 3
            + (pspec(), pspec()), out_specs=pspec(), check_vma=False)
    tree, tally = jax.jit(fn)(
        jnp.asarray(binned), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(N), jnp.ones(F, bool), jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(np.asarray, tree), [int(x) for x in tally]


# -- a plain replay of the round loop, from the tree's own splits --------------


def _replay_leafwise(tree, cfg):
    """A grower allocates child slots in the order it splits, so the splits
    sorted by their left child are the splits in order; a round takes the
    next of them, ``leaf_batch`` at most, whose parents it found there."""
    widths = growth.run_tally_layout(cfg)
    per_split = 1 if growth._sibling_is_derived(cfg.quantized_grad) else 2
    batch = max(1, min(cfg.leaf_batch, cfg.num_leaves - 1))
    parents = sorted(np.flatnonzero(~tree.is_leaf),
                     key=lambda p: tree.left[p])
    tally = [1, 1] + [0] * (len(widths) - 1)       # the root's pass
    nodes, at = 1, 0
    while at < len(parents):
        k = 0
        while (at + k < len(parents) and k < batch
               and parents[at + k] < nodes):
            k += 1
        assert [int(tree.left[p]) for p in parents[at:at + k]] == [
            nodes + 2 * j for j in range(k)] and k
        live = per_split * k
        tally[0] += live
        tally[1 + min(i for i, w in enumerate(widths) if i and w >= live)] += 1
        nodes, at = nodes + 2 * k, at + k
    assert nodes == int(tree.node_count)
    return tally


def _replay_depthwise(tree, cfg):
    """A level's pass holds every node of its depth; it runs while leaves
    are left to spend and the level has nodes."""
    widths = growth.run_tally_layout(cfg)
    depth = np.zeros(tree.left.size, int)
    for p in np.flatnonzero(~tree.is_leaf):        # parents lie under kids
        depth[tree.left[p]] = depth[tree.right[p]] = depth[p] + 1
    depth = depth[:int(tree.node_count)]
    inner = ~tree.is_leaf[:int(tree.node_count)]
    tally, leaves = [0] * (1 + len(widths)), 1
    for level in range(len(widths)):
        here = int((depth == level).sum())
        if leaves >= cfg.num_leaves or not here:
            break
        tally[0] += here
        tally[1 + level] += 1
        leaves += int((inner & (depth == level)).sum())
    return tally


SHAPES = {
    "full": dict(),
    # 4096 rows cannot fill 31 leaves of 400: the tree stops short
    "short": dict(min_data_in_leaf=400),
    "min_gain": dict(min_gain_to_split=5.0),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("stats", ["int8", "float"])
@pytest.mark.parametrize("leaf_batch", [1, 8])
def test_a_leafwise_tally_is_the_replay_of_the_tree_s_own_rounds(
        leaf_batch, stats, shape):
    cfg = _cfg(stats, leaf_batch=leaf_batch, **SHAPES[shape])
    tree, tally = _grown(cfg, _rows("two_splits" if shape == "min_gain"
                                    else "full"))
    assert tally == _replay_leafwise(tree, cfg)
    nodes = int(tree.node_count)
    assert {"full": nodes == 61, "short": 3 < nodes < 61,
            "min_gain": nodes == 5}[shape]
    if shape == "full" and leaf_batch == 8:
        # rounds of 1, 2, 4, 8, 8, 7 splits: 31 of 37 slots, 61 of 65
        assert tally == ([31, 1, 3, 3] if stats == "int8"
                         else [61, 1, 2, 1, 3])


@pytest.mark.parametrize("stats", ["int8", "float"])
@pytest.mark.parametrize("kw", [dict(), dict(max_depth=3),
                                dict(min_data_in_leaf=400)],
                         ids=["slack", "max_depth3", "short"])
def test_a_depthwise_tally_is_one_entry_a_level_that_ran(kw, stats):
    cfg = _cfg(stats, growth_policy="depthwise", **kw)
    tree, tally = _grown(cfg)
    widths = growth.run_tally_layout(cfg)
    assert tally == _replay_depthwise(tree, cfg)
    assert widths[:4] == (1, 2, 4, 8)[:len(widths)]
    assert len(widths) == (3 if "max_depth" in kw else 7)
    assert tally[1] == 1 and set(tally[1:]) <= {0, 1}


@pytest.mark.parametrize("policy", ["leafwise", "depthwise"])
@pytest.mark.parametrize("blocks", [2, 4])
def test_a_blocked_pass_is_one_pass(blocks, policy):
    """``hist_blocks`` launches a kernel a block; the tally counts passes,
    and the blocked fold of one shard grows the plain program's tree here
    (one shard, the fold exact enough on these rows to pick the splits)."""
    cfg = _cfg("float", growth_policy=policy, hist_blocks=blocks)
    tree, tally = _grown(cfg)
    replay = _replay_depthwise if policy == "depthwise" else _replay_leafwise
    assert tally == replay(tree, cfg)
    assert tally == _grown(cfg._replace(hist_blocks=0))[1]


@pytest.mark.parametrize("stats", ["int8", "float"])
@pytest.mark.parametrize("policy", ["leafwise", "depthwise"])
def test_four_shards_carry_the_one_device_tally(policy, stats):
    """Under ``hist_blocks`` 4 the trees are the same to the bit on one
    device and on four, and so is what ran; the plain ``psum`` build's tally
    is the replay of its own tree. Every shard holds the same tally
    (``out_specs`` replicated)."""
    cfg = _cfg(stats, growth_policy=policy, hist_blocks=4)
    one_tree, one = _grown(cfg, shards=1)
    four_tree, four = _grown(cfg, shards=4)
    assert one_tree.feat.tobytes() == four_tree.feat.tobytes()
    assert one == four
    cfg = cfg._replace(hist_blocks=0)
    tree, tally = _grown(cfg, shards=4)
    replay = _replay_depthwise if policy == "depthwise" else _replay_leafwise
    assert tally == replay(tree, cfg)


# -- every path that downloads trees -------------------------------------------


@pytest.fixture(autouse=True)
def _clean_trace():
    spans.clear_trace()
    yield
    metrics.set_enabled(True)
    spans.clear_trace()


def _table(seed=0):
    binned, grad, _ = _rows(seed=seed)
    return binned.T.astype(np.float32), (grad < 0).astype(np.float32)


@pytest.fixture(scope="module")
def dataset():
    X, y = _table()
    return gb.LightGBMDataset.construct(
        X, y, max_bin=B, mesh=meshlib.make_mesh(devices=jax.devices()[:1]))


PATHS = {
    "fused": dict(),
    "fused_valid": dict(valid=True, early_stopping_rounds=0),
    "fused_valid_stopped": dict(valid=True, early_stopping_rounds=1,
                                num_iterations=40),
    "host_loop": dict(iteration_callback=lambda it, m: None),
    "host_loop_valid": dict(valid=True,
                            iteration_callback=lambda it, m: None),
    "dart": dict(boosting_type="dart"),
    "dart_host_loop": dict(boosting_type="dart",
                           iteration_callback=lambda it, m: None),
}


def _fit(dataset, *, valid=False, cfg=None, num_iterations=3, **kw):
    if valid:
        Xv, yv = _table(1)
        kw["valid_set"] = (Xv[:512], yv[:512], None)
    cfg = cfg or _cfg(num_leaves=15, num_bins=dataset.max_bin)
    booster = gb.train_booster(dataset=dataset, objective="binary",
                               num_iterations=num_iterations, cfg=cfg, **kw)
    fits = [e["args"] for e in spans.get_trace_events()
            if e["name"] == "gbdt_fit"]
    return booster, fits[-1] if fits else None, cfg


def _replayed(booster, cfg, grown_as=lambda t: {}):
    """The fit's attributes from a replay of every tree of the booster;
    ``grown_as(t)``: what of ``cfg`` tree ``t`` was grown under instead."""
    told = dict(passes=0, slots=0, live=0)
    for t in range(booster.num_trees):
        variant = cfg._replace(**grown_as(t))
        widths = growth.run_tally_layout(variant)
        tally = np.asarray(_replay_leafwise(jax.tree_util.tree_map(
            lambda a: a[t], booster.trees), variant))
        told["passes"] += int(tally[1:].sum())
        told["slots"] += int(tally[1:] @ np.asarray(widths))
        told["live"] += int(tally[0])
    return dict(told, launches=told["passes"],
                iterations=booster.num_trees)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_path_tells_what_it_ran(dataset, path):
    booster, fit, cfg = _fit(dataset, **PATHS[path])
    assert fit["path"] == {"dart_host_loop": "dart", "host_loop_valid":
                           "host_loop", "fused_valid_stopped":
                           "fused_valid"}.get(path, path)
    told = {k: fit[k] for k in TOLD}
    if path == "fused_valid_stopped":
        # the trees the stop cut off were grown, and counted
        grown = len(booster.eval_history["binary_logloss"])
        assert booster.num_iterations < grown == told["iterations"] < 40
        kept = _replayed(booster, cfg)
        assert told["passes"] > kept["passes"]
        assert told["slots"] > kept["slots"] and told["live"] > kept["live"]
        assert told["passes"] > grown                   # a root's a tree
    else:
        assert told == _replayed(booster, cfg)


def test_a_quantized_warm_up_tells_both_kinds_of_tree(dataset):
    """``quant_warmup_iters`` grows the first trees in full precision inside
    the same program: their passes sum both children at the float widths, the
    later ones derive; the fit tells the sum of both."""
    cfg = _cfg(num_leaves=15, num_bins=dataset.max_bin, quant_warmup_iters=1)
    booster, fit, _ = _fit(dataset, cfg=cfg)
    want = _replayed(booster, cfg,
                     lambda t: dict(quantized_grad=t >= cfg.quant_warmup_iters))
    assert {k: fit[k] for k in TOLD} == want
    # not what either kind alone would have run
    for q in (False, True):
        assert want["live"] != _replayed(
            booster, cfg, lambda t: dict(quantized_grad=q))["live"]


def test_hist_blocks_launch_a_kernel_a_block(dataset):
    booster, fit, cfg = _fit(dataset, cfg=_cfg(
        "float", num_leaves=15, num_bins=dataset.max_bin, hist_blocks=4))
    want = _replayed(booster, cfg)
    assert fit["launches"] == 4 * want["passes"]
    assert {k: fit[k] for k in TOLD if k != "launches"} == {
        k: v for k, v in want.items() if k != "launches"}


def test_a_mesh_fit_tells_the_one_shard_s_tally():
    X, y = _table()
    told = {}
    for shards in (1, 4):
        spans.clear_trace()
        ds = gb.LightGBMDataset.construct(X, y, max_bin=B, mesh=meshlib.make_mesh(
            devices=jax.devices()[:shards]))
        _, fit, _ = _fit(ds, cfg=_cfg("float", num_leaves=15,
                                      num_bins=ds.max_bin, hist_blocks=4))
        assert fit["shards"] == shards
        told[shards] = {k: fit[k] for k in TOLD}
    assert told[1].pop("launches") == 4 * told[4].pop("launches")
    assert told[1] == told[4]


def _runs():
    series = (metrics.get_registry().snapshot().get(
        "gbdt_hist_passes_run_total") or {}).get("series", [])
    return {s["labels"]["width"]: s["value"] for s in series}


@pytest.mark.parametrize("stats,leaf_batch", [("int8", 8), ("float", 8),
                                              ("int8", 1)])
def test_the_counter_moves_by_exactly_the_runs(dataset, stats, leaf_batch):
    cfg = _cfg(stats, num_bins=dataset.max_bin, leaf_batch=leaf_batch)
    before = _runs()
    booster, fit, _ = _fit(dataset, cfg=cfg, num_iterations=2)
    moved = {w: v - before.get(w, 0) for w, v in _runs().items()
             if v != before.get(w, 0)}
    widths = growth.run_tally_layout(cfg)
    total = sum(np.asarray(_replay_leafwise(jax.tree_util.tree_map(
        lambda a: a[t], booster.trees), cfg)) for t in range(2))
    want = {"root": 2}
    for w, runs in zip(widths[1:], total[2:]):
        if runs:
            want[str(w)] = runs
    assert moved == want and sum(moved.values()) == fit["passes"]
    # at leaf_batch 1 a round's pass is one slot wide too: not the root's
    assert leaf_batch != 1 or moved == {"root": 2, "1": 60}


@pytest.mark.parametrize("path", ["fused", "fused_valid", "host_loop",
                                  "dart"])
def test_telemetry_off_records_nothing_and_grows_the_same_trees(dataset,
                                                                path):
    on, fit, _ = _fit(dataset, **PATHS[path])
    assert fit["passes"]
    before = _runs()
    spans.clear_trace()
    metrics.set_enabled(False)
    off, fit, _ = _fit(dataset, **PATHS[path])
    metrics.set_enabled(True)
    assert fit is None and spans.get_trace_events() == []
    assert _runs() == before
    assert off.model_string() == on.model_string()


# -- the program but for the tally ----------------------------------------------


def _sites(jaxpr, names, found):
    """``(primitive, operand avals, result avals)`` of every equation of one
    of ``names``, in program order, through every sub-jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            found.append((eqn.primitive.name,
                          tuple(str(v.aval) for v in eqn.invars),
                          tuple(str(v.aval) for v in eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _sites(sub, names, found)
    return found


def _program(cfg, shards, tallied):
    """``grow_tree`` as a fit stages it (``tallied``) or as a caller that
    takes no tally does."""
    axis = "data" if shards > 1 else None

    def fn(b, g, h, v, fm, key):
        got = [] if tallied else None
        tree, row_node = growth.grow_tree(b, g, h, v, fm, cfg, axis, None,
                                          key, run_tally=got)
        return (tree, row_node) + tuple(got or ())

    if axis:
        fn = shard_map(fn, mesh=meshlib.make_mesh(
            devices=jax.devices()[:shards]),
            in_specs=(pspec(None, "data"),) + (pspec("data"),) * 3
            + (pspec(), pspec()),
            out_specs=(pspec(), pspec("data")) + (pspec(),) * tallied,
            check_vma=False)
    return fn, (jnp.zeros((F, 2048), jnp.uint8), jnp.zeros(2048),
                jnp.ones(2048), jnp.ones(2048), jnp.ones(F, bool),
                jax.random.PRNGKey(0))


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("stats", ["int8", "float"])
def test_the_program_differs_by_the_tally_alone(stats, shards, monkeypatch):
    """The tallied program with its tally dropped lowers to the text of the
    program without one (which the pinned hashes of test_gbdt_pass_width and
    test_gbdt_allreduce hold to the parent's, byte for byte); with the tally
    kept, the kernel calls and the reductions are the same equations on the
    same operands, in the same order."""
    monkeypatch.setenv("MMLSPARK_TPU_HIST_ENGINE", "pallas")
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
    cfg = _cfg(stats, num_bins=255)
    with_tally, args = _program(cfg, shards, True)
    without, _ = _program(cfg, shards, False)

    def text(fn):
        return jax.jit(fn).lower(*args).as_text().split("\n", 1)[1]

    assert text(lambda *a: with_tally(*a)[:2]) == text(without)
    assert text(with_tally) != text(without)
    names = ("pallas_call", "psum", "pmax", "all_gather")
    kept = _sites(jax.make_jaxpr(with_tally)(*args).jaxpr, names, [])
    assert kept == _sites(jax.make_jaxpr(without)(*args).jaxpr, names, [])
    assert [s[0] for s in kept].count("pallas_call") == 1 + len(
        growth.run_tally_layout(cfg)[1:])
    assert ("psum" in [s[0] for s in kept]) == (shards > 1)
