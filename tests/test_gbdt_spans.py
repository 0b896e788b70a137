"""The fit's own story: host spans that tile ``train_booster``, jax's compile
stages as their children, and layer names on the device program
(docs/observability.md, "Spans and device traces")."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.models.gbdt import booster as gb
from mmlspark_tpu.models.gbdt import growth
from mmlspark_tpu.observability import metrics, spans
from mmlspark_tpu.ops import histogram as hist_ops

FUSED_CHILDREN = ["gbdt_fit_prepare", "gbdt_fit_program", "gbdt_fit_dispatch",
                  "gbdt_fit_wait", "gbdt_fit_download", "gbdt_fit_finalize"]
SCOPES = ["gbdt_grad", "gbdt_quantize", "gbdt_hist", "gbdt_split_find",
          "gbdt_route", "gbdt_tree_update", "gbdt_renew_leaf",
          "gbdt_score_update"]
KERNELS = ["gbdt_hist_kernel", "gbdt_node_hist_kernel"]
ROWS, FEATURES = 4104, 5               # a shape no other test file fits


def _data(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(ROWS, FEATURES)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def dataset():
    X, y = _data()
    return gb.LightGBMDataset.construct(X, y, max_bin=31)


@pytest.fixture(autouse=True)
def _clean_trace():
    spans.clear_trace()
    yield
    metrics.set_enabled(True)
    spans.clear_trace()


def _fit(dataset, path="fused", seed=2626, **kw):
    cfg = growth.GrowConfig(num_leaves=5, min_data_in_leaf=5)
    if path == "fused_valid":
        Xv, yv = _data(1)
        kw.update(valid_set=(Xv[:512], yv[:512], None),
                  early_stopping_rounds=2)
    elif path == "host_loop":
        kw.update(iteration_callback=lambda it, m: None)
    elif path == "dart":
        kw.update(boosting_type="dart")
    return gb.train_booster(dataset=dataset, objective="binary",
                            num_iterations=3, cfg=cfg, seed=seed, **kw)


def _fits_and_children():
    """``[(fit event, [its child events in start order])]``, oldest first."""
    events = [e for e in spans.get_trace_events() if e["ph"] == "X"]
    out = []
    for fit in (e for e in events if e["name"] == "gbdt_fit"):
        lo, hi = fit["ts"], fit["ts"] + fit["dur"]
        inside = [e for e in events if e is not fit
                  and lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1.0]
        out.append((fit, sorted(inside, key=lambda e: e["ts"])))
    return out


@pytest.mark.parametrize("path,expected", [
    ("fused", FUSED_CHILDREN),
    ("fused_valid", FUSED_CHILDREN),
    ("dart", FUSED_CHILDREN),
    ("host_loop", ["gbdt_fit_prepare", "gbdt_fit_rounds",
                   "gbdt_fit_finalize"]),
])
def test_a_fit_is_one_span_tiled_by_its_phases(dataset, path, expected):
    _fit(dataset, path)
    (fit, inside), = _fits_and_children()
    children = [e for e in inside if e["args"].get("parent") == "gbdt_fit"]
    assert [e["name"] for e in children] == expected
    assert fit["args"]["path"] == path
    # the host loop's step program is every path's: the fits above built it
    assert fit["args"]["program"] == (
        "hit" if path == "host_loop" else "built")
    assert (fit["args"]["trees"], fit["args"]["rows"],
            fit["args"]["features"]) == (3, ROWS, FEATURES)
    assert "parent" not in fit["args"]
    # back to back: a first fit is long enough for the seams not to count
    assert sum(e["dur"] for e in children) >= 0.95 * fit["dur"]
    for a, b in zip(children, children[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0


def _float_sums():
    series = (metrics.get_registry().snapshot().get(
        "gbdt_float_sums_total") or {}).get("series", [])
    return {(s["labels"]["site"], s["labels"]["form"]): s["value"]
            for s in series}


@pytest.mark.parametrize("policy", ["leafwise", "depthwise"])
def test_fit_span_says_its_stats_and_a_float_build_counts_its_sum_sites(
        dataset, policy):
    """``gbdt_fit`` carries ``stats``; building a float fit's program counts
    every site that sums at the node's own magnitude (once a place it is
    staged out), and an int8 fit's build counts none."""
    def fit(seed, **cfg):
        cfg = growth.GrowConfig(num_leaves=5, min_data_in_leaf=5,
                                growth_policy=policy, **cfg)
        return gb.train_booster(dataset=dataset, objective="binary",
                                num_iterations=2, cfg=cfg, seed=seed)

    before = _float_sums()
    fit(3301)
    (span, _), = _fits_and_children()
    assert span["args"]["stats"] == "bf16"
    moved = {k for k, v in _float_sums().items() if v > before.get(k, 0)}
    assert moved == {("right_side", "suffix_sum"),
                     ("child_totals", "candidate_pair"),
                     ("node_totals", "own_histogram")}
    spans.clear_trace()
    before = _float_sums()
    fit(3302, quantized_grad=True, quant_warmup_iters=0,
        quant_renew_leaf=False)
    (span, _), = _fits_and_children()
    assert span["args"]["stats"] == "int8"
    assert _float_sums() == before


def test_a_fit_nests_under_the_callers_span(dataset):
    with spans.span("caller"):
        _fit(dataset)
    (fit, _), = _fits_and_children()
    assert fit["args"]["parent"] == "caller"


def test_first_fit_builds_and_compiles_second_hits(dataset):
    _fit(dataset, seed=2627)
    _fit(dataset, seed=2627)
    (first, in_first), (second, in_second) = _fits_and_children()
    under_dispatch = {e["name"] for e in in_first
                      if e["args"].get("parent") == "gbdt_fit_dispatch"}
    assert {"gbdt_jax_trace", "gbdt_xla_compile"} <= under_dispatch
    assert first["args"]["program"] == "built"
    stage = [e for e in in_first if e["name"] == "gbdt_jax_trace"]
    assert any(e["args"].get("fun_name") == "multi_local" for e in stage)
    assert second["args"]["program"] == "hit"
    assert not [e for e in in_second if e["name"] in (
        "gbdt_jax_trace", "gbdt_jax_lower", "gbdt_xla_compile",
        "gbdt_cache_load")]


def test_kill_switch_records_nothing_and_changes_no_byte(dataset):
    enabled = _fit(dataset, seed=2628).model_string()
    assert spans.get_trace_events()
    spans.clear_trace()
    metrics.set_enabled(False)
    disabled = _fit(dataset, seed=2628).model_string()
    assert spans.get_trace_events() == []
    assert disabled == enabled


def test_span_names_are_fixed_and_carry_no_digits(dataset):
    for path in ("fused", "host_loop"):
        _fit(dataset, path, seed=2629)
    X, y = _data()
    gb.LightGBMDataset.construct(X[:600], y[:600], max_bin=15)
    names = {e["name"] for e in spans.get_trace_events()}
    assert {"gbdt_fit", "gbdt_dataset"} <= names
    assert not [n for n in names if re.search(r"\d", n)]


def test_dataset_construction_is_one_span_with_four_phases():
    X, y = _data()
    gb.LightGBMDataset.construct(X[:700], y[:700], max_bin=15)
    events = spans.get_trace_events()
    ds, = [e for e in events if e["name"] == "gbdt_dataset"]
    assert (ds["args"]["rows"], ds["args"]["features"]) == (700, FEATURES)
    assert [e["name"] for e in events
            if e["args"].get("parent") == "gbdt_dataset"] == [
        "gbdt_binner_fit", "gbdt_dataset_xfer", "gbdt_dataset_bin",
        "gbdt_dataset_aux"]


def test_record_finished_needs_an_open_span():
    spans.record_finished("late", 0.5, fun_name="f")
    assert spans.get_trace_events() == []
    with spans.span("outer"):
        spans.record_finished("late", 0.25, fun_name="f")
    late, outer = spans.get_trace_events()
    assert late["name"] == "late" and late["dur"] == pytest.approx(0.25e6)
    assert (late["args"]["fun_name"], late["args"]["parent"]) == (
        "f", "outer")
    # it ends now: inside the span that was open, however long it says it was
    assert late["ts"] + late["dur"] <= outer["ts"] + outer["dur"]
    metrics.set_enabled(False)
    with spans.span("outer"):
        spans.record_finished("late", 0.25)
    assert len(spans.get_trace_events()) == 2


@pytest.fixture(scope="module")
def lowered_text(dataset):
    """Lowered text, with debug info, of a toy ``grow_tree`` of each policy,
    of the fused fit program around them and of the plain histogram entry,
    on the Pallas engine (interpreted: this is a CPU)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MMLSPARK_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("MMLSPARK_TPU_HIST_ENGINE", "pallas")
        n, F = 8192, 4
        binned = jnp.zeros((F, n), jnp.uint8)
        ones = jnp.ones(n)
        texts = []
        for policy in ("leafwise", "depthwise"):
            cfg = growth.GrowConfig(
                num_leaves=4, num_bins=16, quantized_grad=True,
                growth_policy=policy)
            grow = (growth.grow_tree if policy == "leafwise"
                    else growth.grow_tree_depthwise)
            texts.append(jax.jit(lambda b, g, h, v: grow(
                b, g, h, v, jnp.ones(F, bool), cfg,
                qkey=jax.random.PRNGKey(0))).lower(
                    binned, ones, ones, ones).as_text(debug_info=True))
        texts.append(jax.jit(lambda b, s: hist_ops.histogram_cols(
            b, s, 16)).lower(binned, jnp.ones((3, n))).as_text(
                debug_info=True))
        _fit(dataset, seed=2630)
        key, = [k for k in gb._STEP_CACHE
                if k[-1] == "fused" and k[-2] == 2630]
        scores = gb._device_tile_scores(jnp.zeros(1, jnp.float32),
                                        dataset.n_pad, 1, dataset.mesh)
        texts.append(gb._STEP_CACHE[key].lower(
            dataset.Xbt_d, dataset.y_d, dataset.w_d, dataset.vmask_d,
            scores).as_text(debug_info=True))
    return texts


@pytest.mark.parametrize("name", SCOPES + KERNELS)
def test_device_program_names_its_layers(lowered_text, name):
    pattern = re.compile(r"\b" + name + r"\b")
    hits = [i for i, t in enumerate(lowered_text) if pattern.search(t)]
    assert hits, name
    if name not in ("gbdt_grad", "gbdt_score_update", "gbdt_hist_kernel"):
        assert {0, 1} <= set(hits)      # both growth policies carry it


def _valid_counts():
    snap = metrics.get_registry().snapshot()
    evals = {(s["labels"]["metric"], s["labels"]["where"]): s["value"]
             for s in (snap.get("gbdt_valid_metric_total") or {}).get(
                 "series", [])}
    rows = sum(s["value"] for s in (snap.get("gbdt_valid_rows_total")
                                    or {}).get("series", []))
    return evals, rows


@pytest.mark.parametrize("path,where", [("fused_valid", "device"),
                                        ("host_loop", "host")])
def test_validated_fit_says_its_rows_and_metric_and_counts_its_evaluations(
        dataset, path, where):
    """``gbdt_fit`` gains ``valid_rows`` and ``metric``;
    ``gbdt_valid_metric_total{metric, where}`` counts once an evaluation the
    history records, by where the round loop read it, and
    ``gbdt_valid_rows_total`` the held-out rows scored for them."""
    Xv, yv = _data(1)
    held = gb.LightGBMDataset.construct(Xv[:520], yv[:520],
                                        reference=dataset)
    spans.clear_trace()
    (evals0, rows0), kw = _valid_counts(), {}
    if path == "host_loop":
        kw["iteration_callback"] = lambda it, m: None
    b = gb.train_booster(
        dataset=dataset, objective="binary", num_iterations=4, seed=3501,
        cfg=growth.GrowConfig(num_leaves=5, min_data_in_leaf=5),
        valid_set=held, eval_metric_name="auc", early_stopping_rounds=3,
        **kw)
    (fit, _), = _fits_and_children()
    assert fit["args"]["path"] == path
    assert (fit["args"]["valid_rows"], fit["args"]["metric"]) == (520, "auc")
    recorded = len(b.eval_history["auc"])
    assert 1 <= recorded <= 4
    evals, rows = _valid_counts()
    moved = {k: v - evals0.get(k, 0) for k, v in evals.items()
             if v != evals0.get(k, 0)}
    assert moved == {("auc", where): recorded}
    assert rows - rows0 == recorded * 520


def test_unvalidated_fit_has_no_validation_attributes(dataset):
    evals0, rows0 = _valid_counts()
    _fit(dataset, seed=3502)
    (fit, _), = _fits_and_children()
    assert "valid_rows" not in fit["args"] and "metric" not in fit["args"]
    assert _valid_counts() == (evals0, rows0)


def test_validated_program_names_its_scorer_and_metric(dataset):
    Xv, yv = _data(1)
    held = gb.LightGBMDataset.construct(Xv[:520], yv[:520],
                                        reference=dataset)
    gb.train_booster(
        dataset=dataset, objective="binary", num_iterations=2, seed=3503,
        cfg=growth.GrowConfig(num_leaves=5, min_data_in_leaf=5),
        valid_set=held, eval_metric_name="auc", early_stopping_rounds=2)
    key, = [k for k in gb._STEP_CACHE
            if k[-1] == "fused_valid" and k[2] == 3503]
    scores = gb._device_tile_scores(jnp.zeros(1, jnp.float32),
                                    dataset.n_pad, 1, dataset.mesh)
    vscores = gb._device_tile_scores(jnp.zeros(1, jnp.float32),
                                     held.n_pad, 1, held.mesh)
    text = gb._STEP_CACHE[key].lower(
        dataset.Xbt_d, dataset.y_d, dataset.w_d, dataset.vmask_d, scores,
        held.Xbt_d, held.y_d, held.eval_weight(), vscores).as_text(
            debug_info=True)
    for name in ("gbdt_valid_score", "gbdt_valid_metric", "gbdt_hist",
                 "gbdt_route"):
        assert re.search(r"\b" + name + r"\b", text), name
    # the round is in the loop's body and nowhere else: staged once
    assert text.count("stablehlo.while") >= 1
