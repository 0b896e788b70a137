"""The validated cell ``criteo255q.trainval`` (driver ``lib/gbdt_trainval``)
rehearsed on the CPU: its files load and say what the issue asked of them; a
toy table through the whole harness ends ``correct`` with every number read;
the bf16-margin control put in the program's place does not, by ``auc_gap``;
nor does a run with a fault of ``faults_val.py`` planted, each by the number
meant to catch it; the two readers read what the driver and a trace give
them and ``None`` where there is nothing. Limits: the toy's own
(``data/toy_limits_trainval.json``)."""

import json
import os

import numpy as np
import pytest

import faults
import faults_val
import run as harness
from conftest import BENCH, ROOT

CELL = "criteo255q.trainval"
TOY = json.load(open(os.path.join(BENCH, "tests", "data",
                                  "toy_limits_trainval.json")))


def _drive(monkeypatch, capsys, extra=()):
    """The harness's whole run in this process, on the toy table."""
    real = harness.load_cell

    def toy_cell(name):
        spec = real(name)
        spec["workload"]["limits"] = TOY[name]
        return spec

    monkeypatch.setattr(harness, "load_cell", toy_cell)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert harness.main(["--workload", CELL, "--seed", str(TOY["seed"]),
                         "--seconds", "0.1", "--trace", "1", "--rows",
                         str(TOY["rows"]), *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cells_files_load_and_say_what_was_asked():
    spec = harness.load_cell(CELL)
    config, workload, cell = spec["config"], spec["workload"], spec["cell"]
    assert (config["name"], workload["kind"], cell["traffic"],
            cell["chips"]) == ("criteo-lgbm-255q-val", "gbdt_trainval",
                               "trainval", 1)
    sibling = json.load(open(os.path.join(BENCH, "configs",
                                          "criteo-lgbm-255q.json")))
    for key in ("data", "params", "published", "stats_dtype"):
        assert config[key] == sibling[key], key
    chunk = config["data"]["chunk_rows"]
    v = config["validation"]
    assert config["rows"] == 133 * chunk == 65_372_160
    assert config["valid_rows"] == v["chunks"] * chunk == 2_949_120
    assert config["rows"] + config["valid_rows"] == sibling["rows"]
    assert v["first_chunk"] * chunk == config["rows"]
    assert (v["metric"], v["early_stopping_rounds"],
            v["metric_eval_period"]) == ("auc", 50, 1)
    assert config["reduced"] == ["rows"] and "guarantees" in config
    assert (workload["trees_per_fit"], workload["fit_seed"]) == (2, 0)
    assert set(workload["limits"]) == set(TOY[CELL]) | {"other_engines"}
    bench = spec["bench"]
    entry, = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert entry["file"].endswith("criteo-lgbm-255q-val.json")
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {"valid_eval_share_pct", "valid_metric_host_evals"}
    assert {m["moves"] for m in mine.values()} == {"train_trees_per_s"}
    assert mine["valid_eval_share_pct"]["layer"] == "validation"


def _read_host_evals_here(monkeypatch):
    # a CPU rehearsal's line leaves the metric out, as it does every metric
    # but round_loop_compiles (test_cli.py); asked for, the harness reads it
    from layer_metrics import valid_metric_host_evals
    monkeypatch.setattr(valid_metric_host_evals, "NEEDS_CHIP", False,
                        raising=False)


def test_sound_run_is_correct_and_the_bf16_margin_control_is_not(monkeypatch,
                                                                 capsys):
    _read_host_evals_here(monkeypatch)
    sound = _drive(monkeypatch, capsys)
    assert sound["correct"] is True, sound["compared"]
    assert {k for k, row in sound["compared"].items()
            if row["value"] is None} == set()
    # one dispatch a fit: the metric was never read on the host
    assert sound["metrics"]["valid_metric_host_evals"]["value"] == 0.0
    control = _drive(monkeypatch, capsys, extra=("--control", "1"))
    assert control["correct"] is False
    gap = control["compared"]["auc_gap"]
    assert gap["value"] > 3 * gap["limit"] >= \
        3 * sound["compared"]["auc_gap"]["value"]


@pytest.mark.parametrize("fault,caught_by", [
    ("ties_by_position", "auc_gap"),
    ("margin_not_carried", "auc_gap"),
    ("training_labels", "auc_gap"),
    ("state_unchanged", "leaf_gap"),
    ("half_batch", "count_gap"),
    ("altered_answer", "leaf_gap"),
])
def test_broken_timed_path_is_not_correct(fault, caught_by, monkeypatch,
                                          capsys):
    mend = (faults_val if fault in faults_val.FAULTS else faults).plant(fault)
    try:
        line = _drive(monkeypatch, capsys)
    finally:
        mend()
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > 3 * row["limit"]


def test_a_fit_that_falls_to_the_host_loop_is_counted(monkeypatch, capsys):
    monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_VALID", "1")
    _read_host_evals_here(monkeypatch)
    line = _drive(monkeypatch, capsys)
    assert line["correct"] is True, line["compared"]
    evals = line["metrics"]["valid_metric_host_evals"]["value"]
    assert evals == 2.0 * line["attempted"]


def test_reference_auc_counts_pairs_exactly():
    from lib import reference_auc
    rng = np.random.default_rng(0)
    margin = np.round(rng.normal(size=3000), 1)
    positive = margin + rng.normal(size=3000) > 0.5
    pairs = ((margin[positive][:, None] > margin[~positive][None, :]).sum()
             + 0.5 * (margin[positive][:, None]
                      == margin[~positive][None, :]).sum())
    assert reference_auc.auc_exact(margin, positive) == pairs / (
        positive.sum() * (~positive).sum())
    assert reference_auc.auc_exact(np.zeros(10), np.arange(10) < 3) == 0.5
    assert reference_auc.auc_exact(margin, np.zeros(3000, bool)) == 0.5


def test_readers_read_a_trace_and_none():
    from layer_metrics import valid_eval_share_pct as share
    from layer_metrics import valid_metric_host_evals as evals
    ops = {
        "%fusion.1 = s32[2949120]{0} fusion(u8[39,2949120]{1,0} %p)": [0.3, 60],
        "%sort.2 = (f32[2949120]{0}, f32[2949120]{0}) sort(%a, %b)": [0.1, 2],
        "%k = s32[39,96,128] custom-call(u8[39,65372160]{1,0} %x)": [3.0, 6],
        "%f = f32[12949120]{0} fusion(f32[29491200]{0} %y)": [9.0, 1],
    }
    ctx = {"facts": {"valid_rows": 2949120},
           "trace": {"ops": ops, "busy_s": 4.0, "devices": 1}}
    assert share.held_out_seconds(ops, 2949120) == (0.4, 62)
    assert share.read(ctx) == pytest.approx(10.0)
    # no held-out rows in the facts, none in the trace, no trace at all
    assert share.read({"facts": {}, "trace": ctx["trace"]}) is None
    assert share.read({"facts": {"valid_rows": 123},
                       "trace": ctx["trace"]}) is None
    assert share.read({"facts": {"valid_rows": 2949120},
                       "trace": None}) is None
    assert evals.read({"facts": {"valid_metric_evals": {
        "device": 36, "host": 0}}}) == 0.0
    assert evals.read({"facts": {}}) is None
    for reader in (share, evals):
        assert (reader.MOVES, reader.UNIT) in (("train_trees_per_s", "%"),
                                               ("train_trees_per_s", "count"))
