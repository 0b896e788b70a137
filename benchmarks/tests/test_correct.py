"""What decides ``correct`` can fail: the lower-precision control put in the
program's place comes out as not correct, and so does a run of the harness
with the timed path broken underneath. Toy size on the CPU, so the limits
are the toy's own (``data/toy_limits.json``: the cell's exact limits, and
gaps read at this size the way PERF.md sets the cell's from chip readings);
the chip-size readings are in PERF.md."""

import io
import json
import os

import pytest

import faults
import run as harness
from conftest import BENCH, ROOT

CELLS = [w["name"] for w in json.load(open(os.path.join(
    ROOT, "BENCHMARK.json")))["workloads"]]
TOY = json.load(open(os.path.join(BENCH, "tests", "data",
                                  "toy_limits.json")))


def _drive(cell, monkeypatch, capsys, extra=()):
    """The harness's whole run in this process, on the toy table."""
    real = harness.load_cell

    def toy_cell(name):
        spec = real(name)
        spec["workload"]["limits"] = TOY[name]
        return spec

    monkeypatch.setattr(harness, "load_cell", toy_cell)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert harness.main(["--workload", cell, "--seed", "11", "--seconds",
                         "0.1", "--trace", "0", "--rows", str(TOY["rows"]),
                         *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell, monkeypatch, capsys):
    sound = _drive(cell, monkeypatch, capsys)
    assert sound["correct"] is True, sound["compared"]
    control = _drive(cell, monkeypatch, capsys, extra=("--control", "1"))
    assert control["correct"] is False
    loss = control["compared"]["gain_loss"]
    assert loss["value"] > loss["limit"] >= \
        sound["compared"]["gain_loss"]["value"]
    assert loss["value"] >= 3 * sound["compared"]["gain_loss"]["value"]


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "leaf_gap"),
    ("half_batch", "count_gap"),
    ("altered_answer", "leaf_gap"),
])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, caught_by,
                                          monkeypatch, capsys):
    mend = faults.plant(fault)
    try:
        line = _drive(cell, monkeypatch, capsys)
    finally:
        mend()
    assert line["correct"] is False
    row = line["compared"][caught_by]
    assert row["value"] > 3 * row["limit"]
