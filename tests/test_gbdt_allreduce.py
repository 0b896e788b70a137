"""The cross-shard reductions of tree growth (``growth._allreduce``).

Every collective of ``growth.py`` runs under ``gbdt_allreduce`` and is counted
in ``gbdt_allreduce_bytes_total{what, per}`` from its static shape where it is
staged out; on one device nothing is emitted, so that program is the one it
was. The sharded fit is held against the one-device fit of the same table.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _gbdt_reference import route_rows_wn
from mmlspark_tpu.models.gbdt import growth
from mmlspark_tpu.models.gbdt.booster import LightGBMDataset, train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.observability import metrics, spans
from mmlspark_tpu.parallel import mesh as meshlib
from mmlspark_tpu.parallel.compat import shard_map
from mmlspark_tpu.parallel.placement import pspec

_KINDS = ("hist", "totals", "quantize", "votes", "renew")
_COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "ppermute",
                "all_to_all", "reduce_scatter", "psum_scatter")
F, N, B = 6, 8192, 63
_PARENT_JAX = "0.9.0"      # the jax the parent's texts were hashed under


def _moved():
    return {(k, per): metrics.counter("gbdt_allreduce_bytes_total", what=k,
                                      per=per).value
            for k in _KINDS for per in ("tree", "round", "level")}


def _moved_since(before):
    return {k: v - before[k] for k, v in _moved().items() if v != before[k]}


def _table(seed=0):
    """Signals far enough apart that a quantizer's noise cannot reorder the
    first splits: two numeric steps and one category subset."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, F)).astype(np.float32)
    X[:, 5] = rng.integers(0, 12, N)
    logit = (3.0 * (X[:, 0] > 0.3) - 2.0 * (X[:, 1] < -0.5)
             + 1.5 * np.isin(X[:, 5], [1, 4, 7]))
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(1.0 - logit))).astype(
        np.float32)
    return X, y


def _fit(shards, trees=3, **cfg):
    X, y = _table()
    mesh = meshlib.make_mesh(devices=jax.devices()[:shards])
    ds = LightGBMDataset.construct(X, y, max_bin=B, mesh=mesh,
                                   categorical_features=(5,))
    base = dict(num_leaves=7, min_data_in_leaf=20, quantized_grad=True,
                quant_renew_leaf=False, quant_warmup_iters=0, leaf_batch=4)
    return train_booster(dataset=ds, num_iterations=trees,
                         objective="binary",
                         cfg=GrowConfig(**dict(base, **cfg)))


# -- the sharded fit against the one-device fit -------------------------------


def test_four_shard_quantized_fit_matches_one_device_fit():
    """Same splits; leaf values apart by the quantizer's noise only. Each
    shard scales its int8 statistics by its own largest magnitude and draws
    its own rounding bits, so the summed statistics of a leaf differ between
    topologies by about ``sqrt(rows) x step`` (step = amax / 127 here), a few
    parts in a thousand of a leaf's hessian at these sizes; a lost shard or a
    wrong scale moves them by tens of per cent."""
    one, four = _fit(1), _fit(4)
    for name in ("feat", "thr_bin", "left", "right", "is_leaf", "node_count",
                 "cat_bitset"):
        np.testing.assert_array_equal(getattr(one.trees, name),
                                      getattr(four.trees, name), err_msg=name)
    np.testing.assert_array_equal(one.trees.node_cnt, four.trees.node_cnt)
    scale = np.abs(one.trees.leaf_value).max()
    assert np.abs(one.trees.leaf_value
                  - four.trees.leaf_value).max() < 0.02 * scale
    assert (~one.trees.is_leaf).sum() >= 12        # the trees did grow


def test_blocked_reduction_is_bit_identical_across_shard_counts():
    one, four = _fit(1, hist_blocks=4), _fit(4, hist_blocks=4)
    assert one.model_string() == four.model_string()


def test_fit_span_carries_shards():
    spans.clear_trace()
    _fit(4, trees=1)
    _fit(1, trees=1)
    got = [e["args"]["shards"] for e in spans.get_trace_events()
           if e["name"] == "gbdt_fit"]
    assert got == [4, 1]


# -- the counter --------------------------------------------------------------


@pytest.mark.parametrize("batch", [1, 2, 4])
def test_a_built_program_counts_each_site_s_static_bytes(batch):
    """The root's pass moves ``[F, 3, B]`` of f32 once a tree and a round's
    ``[F, 2 * batch * 3, B]`` (both children of every split of the round)
    once a round; the totals are three f32. Counted where the program is
    built, so a fit of several trees counts them once."""
    before = _moved()
    _fit(4, trees=2, leaf_batch=batch)
    assert _moved_since(before) == {
        ("hist", "tree"): F * 3 * B * 4,
        ("hist", "round"): F * 2 * batch * 3 * B * 4,
        ("totals", "tree"): 3 * 4}


def test_depthwise_levels_are_sites_of_their_own():
    """Level ``d`` reduces ``[F, 2^d * 3, B]`` up to the 4 leaves' depth cap
    (2: three levels here)."""
    before = _moved()
    _fit(4, trees=1, growth_policy="depthwise", num_leaves=4)
    assert _moved_since(before) == {
        ("hist", "level"): sum(F * w * 3 * B * 4 for w in (1, 2, 4)),
        ("totals", "tree"): 3 * 4}


def test_one_device_fit_counts_nothing():
    before = _moved()
    _fit(1, trees=1)
    assert _moved_since(before) == {}


@pytest.mark.parametrize("cfg,kinds", [
    (dict(hist_blocks=8), {"hist", "totals", "quantize"}),
    (dict(quant_renew_leaf=True), {"hist", "totals", "renew"}),
    (dict(voting=True, top_k=2), {"hist", "totals", "votes"}),
    (dict(growth_policy="depthwise"), {"hist", "totals"}),
])
def test_every_kind_of_reduction_is_counted(cfg, kinds):
    before = _moved()
    _fit(4, trees=1, **cfg)
    assert {what for what, _ in _moved_since(before)} == kinds


# -- names on the device program ---------------------------------------------


def _collectives(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COLLECTIVES:
            found.append((eqn.primitive.name,
                          str(eqn.source_info.name_stack)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _collectives(sub, found)
    return found


def _grow_args(n):
    return (jnp.zeros((F, n), jnp.uint8), jnp.zeros(n), jnp.ones(n),
            jnp.ones(n), jnp.ones(F, bool), jax.random.PRNGKey(0))


def _grow_fn(cfg, axis_name):
    cfg = GrowConfig(
        num_leaves=7, num_bins=B, min_data_in_leaf=5, quantized_grad=True,
        leaf_batch=4, **cfg)
    grow = (growth.grow_tree_depthwise if cfg.growth_policy == "depthwise"
            else growth.grow_tree)
    is_cat = jnp.asarray([False] * (F - 1) + [True])
    return lambda b, g, h, v, fm, k: grow(b, g, h, v, fm, cfg, axis_name,
                                          is_cat, k)[0]


@pytest.mark.parametrize("cfg,least", [
    (dict(quant_renew_leaf=False), 3),
    (dict(quant_renew_leaf=True), 4),
    (dict(hist_blocks=8), 5),
    (dict(voting=True, top_k=2), 5),
    (dict(growth_policy="depthwise"), 3),
    (dict(growth_policy="depthwise", hist_blocks=4, quant_renew_leaf=True),
     5),
])
def test_every_collective_of_growth_is_under_gbdt_allreduce(cfg, least):
    mesh = meshlib.make_mesh(devices=jax.devices()[:4])
    fn = shard_map(_grow_fn(cfg, "data"), mesh=mesh,
                   in_specs=(pspec(None, "data"),) + (pspec("data"),) * 3
                   + (pspec(), pspec()), out_specs=pspec(), check_vma=False)
    found = _collectives(jax.make_jaxpr(fn)(*_grow_args(1024)).jaxpr, [])
    assert len(found) >= least
    assert [f for f in found if "gbdt_allreduce" not in f[1]] == []
    text = jax.jit(fn).lower(*_grow_args(1024)).as_text(debug_info=True)
    assert "/gbdt_allreduce/" in text


def _one_device_text(policy, debug_info=False):
    text = jax.jit(_grow_fn(dict(growth_policy=policy, quant_renew_leaf=True),
                            None)).lower(*_grow_args(2048)).as_text(
                                debug_info=debug_info)
    return re.sub(r"module @\S+", "module @m", text, count=1)


def _ops_outside_routing(jaxpr, found):
    """Every equation that is not under ``gbdt_route``: its primitive and
    the shapes it gives, in program order."""
    for eqn in jaxpr.eqns:
        if "gbdt_route" in str(eqn.source_info.name_stack):
            continue
        found.append((eqn.primitive.name,
                      tuple(getattr(v.aval, "shape", None)
                            for v in eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _ops_outside_routing(sub, found)
    return found


@pytest.mark.parametrize("policy,sha", [
    ("leafwise",
     "56eff214af13161833825f290b11734c2de229f294ed186abb77a0d7dae1e89f"),
    ("depthwise",
     "a83e9261e89c077a3ead4772f92e4e2932b91d91813f6b1d6d24ccff1ab63892"),
])
def test_one_device_program_is_the_parent_s(policy, sha, monkeypatch):
    """At PR 28's parent (818dfa2) every reduction site read ``if axis_name
    is not None: x = lax.psum(x, axis_name)``: on one device, nothing. So no
    name of the funnel's is in the one-device text with its locations, no
    collective is in the jaxpr, and the text equals the text with the funnel
    taken out. Under the jax the parent was lowered with, the text is the
    parent's own, by its hash; another jax writes another text, and then
    only the first three are held. (On a CPU that keeps the compile-cache
    entries, keyed on the text without its locations. On the chip it does
    not: a Mosaic kernel's body is serialized with its locations, so a line
    moved in any frame above the ``pallas_call`` is a new key.)

    PR 34 changed the leafwise text by design, and re-pinned it: a round's
    histogram pass is staged at the widths of ``growth._pass_widths`` (4 and
    8 at these shapes) inside one ``lax.switch``, where it was one call at
    ``2 * leaf_batch``. The new hash is the text of that program. With the
    rule held to the single width it is still the text PRs 28 to 33 pinned,
    byte for byte, so the switch is all that changed; the depthwise text
    did not move.

    PR 36 changed the leafwise text by design again, and re-pinned it: with
    int8 statistics (this program's) a round's pass holds the left child of
    every split at ``leaf_batch`` slots (one width, 4, at these shapes: no
    switch) and the right child is its parent's int32 sums less the left's
    (``growth._sibling_is_derived``), scaled to f32 afterwards. With the
    derivation held off as well as the rule, the text is still the one PRs
    28 to 33 pinned; the depthwise text did not move, and the float text is
    held in ``tests/test_gbdt_pass_width.py``.

    PR 38 changed both texts by design, and re-pinned them: row routing
    (``growth._route_rows_to_children``) finds a row's candidate by one
    reduction over ``W`` where it made four, and returns the pass position
    that the leafwise caller used to reduce itself. The chain of texts back
    to PR 28's ends here, since the caller's lines moved into the function;
    what is held in its place: with the ``[W, n]`` formula patched back in
    (``_gbdt_reference.route_rows_wn``) the program differs only inside
    ``gbdt_route``, equation for equation, and
    ``tests/test_route_rows.py`` holds whole fits to the formula's trees."""
    text = _one_device_text(policy)
    assert "gbdt_allreduce/" not in _one_device_text(policy, debug_info=True)
    found = _collectives(jax.make_jaxpr(_grow_fn(
        dict(growth_policy=policy), None))(*_grow_args(2048)).jaxpr, [])
    assert found == []
    monkeypatch.setattr(growth, "_allreduce",
                        lambda x, axis_name, *a, **kw: x)
    assert _one_device_text(policy) == text
    if jax.__version__ == _PARENT_JAX:
        assert hashlib.sha256(text.encode()).hexdigest() == sha

    def outside():
        return _ops_outside_routing(jax.make_jaxpr(_grow_fn(
            dict(growth_policy=policy, quant_renew_leaf=True), None))(
                *_grow_args(2048)).jaxpr, [])

    shipped = outside()
    monkeypatch.setattr(growth, "_route_rows_to_children",
                        jax.named_scope("gbdt_route")(route_rows_wn))
    assert outside() == shipped and len(shipped) > 100
