"""The float-gradient cell ``criteo255f.train`` (driver ``lib/gbdt_train_f``)
rehearsed on the CPU: its files load and its limits name what the comparison
reads; a toy table through the whole harness ends ``correct``; the int8
control put in the program's place does not, by ``leaf_sum_gap``; nor does a
run with the timed path broken underneath. Limits: the toy's own
(``data/toy_limits_255f.json``), as ``test_correct.py`` has them."""

import json
import os

import pytest

import faults
import run as harness
from conftest import BENCH

CELL = "criteo255f.train"
TOY = json.load(open(os.path.join(BENCH, "tests", "data",
                                  "toy_limits_255f.json")))


def _drive(monkeypatch, capsys, extra=()):
    """The harness's whole run in this process, on the toy table."""
    real = harness.load_cell

    def toy_cell(name):
        spec = real(name)
        spec["workload"]["limits"] = TOY[name]
        return spec

    monkeypatch.setattr(harness, "load_cell", toy_cell)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert harness.main(["--workload", CELL, "--seed", "11", "--seconds",
                         "0.1", "--trace", "1", "--rows", str(TOY["rows"]),
                         *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cells_files_load_and_its_limits_name_what_is_read(monkeypatch,
                                                               capsys):
    spec = harness.load_cell(CELL)
    config, workload = spec["config"], spec["workload"]
    assert (config["name"], workload["kind"]) == ("criteo-lgbm-255f",
                                                  "gbdt_train_f")
    assert config["stats_dtype"] == "bf16"
    assert config["params"]["quantized_grad"] is False
    old = json.load(open(os.path.join(BENCH, "configs",
                                      "criteo-lgbm-255.json")))
    for key in ("data", "params", "rows", "reduced", "published"):
        assert config[key] == old[key], key
    limits = workload["limits"]
    assert set(limits) == set(TOY[CELL]) | {"other_engines"}
    # a CPU rehearsal's line leaves the metric out, as it does every metric
    # but round_loop_compiles (test_cli.py); asked for, the harness reads it
    from layer_metrics import float_sum_sites
    monkeypatch.setattr(float_sum_sites, "NEEDS_CHIP", False, raising=False)
    sound = _drive(monkeypatch, capsys)
    assert {k for k, row in sound["compared"].items()
            if row["value"] is None} == set()      # the toy's, all read
    assert sound["metrics"]["float_sum_sites"]["value"] == 3.0


def test_sound_run_is_correct_and_control_is_not(monkeypatch, capsys):
    sound = _drive(monkeypatch, capsys)
    assert sound["correct"] is True, sound["compared"]
    control = _drive(monkeypatch, capsys, extra=("--control", "1"))
    assert control["correct"] is False
    gap = control["compared"]["leaf_sum_gap"]
    assert gap["value"] > 3 * gap["limit"] >= \
        3 * sound["compared"]["leaf_sum_gap"]["value"]


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "leaf_gap"),
    ("half_batch", "count_gap"),
    ("altered_answer", "leaf_gap"),
])
def test_broken_timed_path_is_not_correct(fault, caught_by, monkeypatch,
                                          capsys):
    mend = faults.plant(fault)
    try:
        line = _drive(monkeypatch, capsys)
    finally:
        mend()
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > 3 * row["limit"]


def test_parents_arithmetic_fails_the_sums(monkeypatch, capsys):
    """With a right child handed ``parent - left`` again (the arithmetic PR
    33 replaced; at the toy's size it still adds up), the program's sums
    stay sound, so what has to catch it at size is ``leaf_sum_gap``: here a
    node's recorded hessian is put off by the parent's f32 spacing at 68 M
    rows, 0.25, and the reading has to pass its limit."""
    from lib import gbdt_train_f
    real = gbdt_train_f.Driver._tree_arrays

    def off_by_a_spacing(self, booster):
        trees = real(self, booster)
        hess = trees["node_hess"].copy()
        small = int(hess[0, :int(trees["node_count"][0])].argmin())
        hess[0, small] += 0.25
        return dict(trees, node_hess=hess)

    monkeypatch.setattr(gbdt_train_f.Driver, "_tree_arrays", off_by_a_spacing)
    line = _drive(monkeypatch, capsys)
    assert line["correct"] is False
    row = line["compared"]["leaf_sum_gap"]
    assert row["value"] > 3 * row["limit"]


def test_float_sum_sites_reads_a_counter_dump_and_none():
    from layer_metrics import float_sum_sites as reader
    dump = {"right_side": 2, "child_totals": 1, "node_totals": 2,
            "kernel_accum": 0}
    assert reader.read({"facts": {"float_sum_sites": dump}}) == 3.0
    # the parent's program has no such counter: every site reads 0
    assert reader.read({"facts": {"float_sum_sites": dict.fromkeys(
        dump, 0)}}) is None
    assert reader.read({"facts": {}}) is None
