"""The three readers of the program's own spans on a hand-written buffer, and
``tools/layers.py``'s reduction on a small recorded trace
(``data/recorded_scoped_trace.json``: cut from a chip run of PR 26)."""

import json
import os
import sys

import pytest

from conftest import BENCH
from layer_metrics import (fit_host_ms, warmup_compile_load_s,
                           warmup_stage_out_s)
from lib import spantree

sys.path.insert(0, os.path.join(BENCH, "tools"))
import layers  # noqa: E402


def _span(name, start_s, dur_s, parent=None, **attrs):
    args = dict(attrs, **({"parent": parent} if parent else {}))
    return {"name": name, "ph": "X", "ts": start_s * 1e6, "dur": dur_s * 1e6,
            "args": args}


def _fit(t0, wait_s, first=False):
    """One fit's spans from ``t0``: 10 ms prepare, dispatch, ``wait_s``,
    2 ms download, 3 ms finalize; a first fit's dispatch holds the stages."""
    out, t = [], t0
    out.append(_span("gbdt_fit_prepare", t, 0.010, "gbdt_fit"))
    t += 0.010
    dispatch = 4.0 if first else 0.001
    out.append(_span("gbdt_fit_dispatch", t, dispatch, "gbdt_fit"))
    if first:
        # an inner jit's trace lies inside the outer one's: counted once
        out += [_span("gbdt_jax_trace", t + 0.1, 0.2, "gbdt_fit_dispatch"),
                _span("gbdt_jax_trace", t, 1.0, "gbdt_fit_dispatch"),
                _span("gbdt_jax_lower", t + 1.0, 0.5, "gbdt_fit_dispatch"),
                _span("gbdt_cache_load", t + 1.5, 2.0, "gbdt_fit_dispatch"),
                _span("gbdt_xla_compile", t + 1.5, 2.5,
                      "gbdt_fit_dispatch")]
    t += dispatch
    out.append(_span("gbdt_fit_wait", t, wait_s, "gbdt_fit"))
    t += wait_s
    out.append(_span("gbdt_fit_download", t, 0.002, "gbdt_fit"))
    out.append(_span("gbdt_fit_finalize", t + 0.002, 0.003, "gbdt_fit"))
    t += 0.005
    out.append(_span("gbdt_fit", t0, t - t0, path="fused",
                     program="built" if first else "hit"))
    return out, t


@pytest.fixture
def buffer(monkeypatch):
    """Warm-up fit, a stray fit nobody counts, then a window of three."""
    evs, t = _fit(100.0, 5.0, first=True)
    for wait in (1.0, 2.0, 2.5, 3.0):
        more, t = _fit(t + 0.5, wait)
        evs += more
    evs.append({"name": "marker", "ph": "i", "ts": 0.0, "args": {}})
    monkeypatch.setattr(spantree, "events", lambda: [
        e for e in evs if e.get("ph") == "X"])
    return evs


def test_warmup_is_the_first_fit_and_the_window_the_last_attempted(buffer):
    ctx = {"facts": {"attempted": 3}}
    # host time of a window fit: 10 + 1 + 2 + 3 ms, whatever the wait
    assert fit_host_ms.read(ctx) == pytest.approx(16.0)
    assert warmup_stage_out_s.read(ctx) == pytest.approx(1.5)
    assert warmup_compile_load_s.read(ctx) == pytest.approx(2.5)
    warmup, window = spantree.warmup_and_window(spantree.events(), 3)
    assert warmup[0]["args"]["program"] == "built"
    assert [f["dur"] for f, _ in window] == pytest.approx(
        [(w + 0.016) * 1e6 for w in (2.0, 2.5, 3.0)])


@pytest.mark.parametrize("reader,missing", [
    (fit_host_ms, "gbdt_fit_wait"),
    (warmup_stage_out_s, "gbdt_jax_"),
    (warmup_compile_load_s, "gbdt_xla_compile"),
    (fit_host_ms, "gbdt_fit"),
])
def test_a_missing_span_reads_none_not_zero(buffer, monkeypatch, reader,
                                            missing):
    kept = [e for e in spantree.events()
            if not e["name"].startswith(missing)]
    monkeypatch.setattr(spantree, "events", lambda: kept)
    assert reader.read({"facts": {"attempted": 3}}) is None


def test_fewer_fits_than_attempted_plus_warmup_reads_none(buffer):
    for reader in (fit_host_ms, warmup_stage_out_s, warmup_compile_load_s):
        assert reader.read({"facts": {"attempted": 5}}) is None


def test_the_programs_buffer_is_read_where_it_exists():
    from mmlspark_tpu.observability import spans
    spans.clear_trace()
    with spans.span("gbdt_fit"):
        spans.instant("not_a_span")
    names = [e["name"] for e in spantree.events()]
    assert names == ["gbdt_fit"]
    spans.clear_trace()


def test_span_tree_groups_stages_under_their_phase(buffer):
    (fit, inside), = spantree.fits(spantree.events())[:1]
    tree = spantree.tree([fit], inside)
    assert tree["attrs"] == {"path": "fused", "program": "built"}
    assert list(tree["children"]) == [
        "gbdt_fit_prepare", "gbdt_fit_dispatch", "gbdt_fit_wait",
        "gbdt_fit_download", "gbdt_fit_finalize"]
    stages = tree["children"]["gbdt_fit_dispatch"]["children"]
    assert stages["gbdt_jax_trace"] == {"s": pytest.approx(1.0), "n": 2}
    assert stages["gbdt_xla_compile"]["s"] == pytest.approx(2.5)
    covered = sum(c["s"] for c in tree["children"].values())
    assert covered == pytest.approx(tree["s"])


RECORDED = os.path.join(BENCH, "tests", "data", "recorded_scoped_trace.json")


def test_reduction_by_scope_of_the_recorded_trace():
    doc = json.load(open(RECORDED))
    red = layers.reduce_scoped(doc)
    expected = doc["expected"]
    assert red["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert list(red["by_scope"])[0] == "gbdt_route"
    for scope, seconds in expected["by_scope"].items():
        assert red["by_scope"][scope] == pytest.approx(seconds, rel=1e-9)
    assert sum(red["by_scope"].values()) == pytest.approx(red["busy_s"])
    assert red["unscoped_pct"] == pytest.approx(
        100.0 * red["by_scope"]["unscoped"] / red["busy_s"])
    assert {k: v[1] for k, v in red["by_kernel"].items()} == \
        expected["kernel_launches"]


def test_reduction_counts_leaves_only_and_names_the_first_scope():
    events = [
        [["%while.1 = (...) while(...)", "jit(multi_local)/while"], 0, 100],
        [["%fusion.18 = u32[8] fusion(...)",
          "jit(m)/while/body/gbdt_route/jit(take_along_axis)/gather"], 10, 40],
        [["%gbdt_node_hist_kernel.4 = s32[40,48,64] custom-call(u8[40,64] "
          '%p), custom_call_target="tpu_custom_call"',
          "jit(m)/while/body/gbdt_hist/pallas_call"], 50, 20],
        [["%fusion.7 = f32[61] fusion(...)",
          "jit(m)/while/body/vmap(gbdt_split_find)/gbdt_hist/sub"], 70, 10],
        [["%copy.3 = s32[8] copy(...)", ""], 80, 5]]
    red = layers.reduce_scoped({"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events}]}]})
    assert red["by_scope"] == {
        "gbdt_route": pytest.approx(40e-9), "gbdt_hist": pytest.approx(20e-9),
        "gbdt_split_find": pytest.approx(10e-9),
        "unscoped": pytest.approx(5e-9)}
    assert red["by_kernel"] == {
        "gbdt_node_hist_kernel": [pytest.approx(20e-9), 1]}
    assert red["unscoped_pct"] == pytest.approx(100 * 5 / 75)
    assert layers.reduce_scoped({"planes": []})["unscoped_pct"] is None
