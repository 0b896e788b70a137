"""The four readers of a fit's run tally (``passes``, ``launches``,
``slots``, ``live`` on the program's ``gbdt_fit`` spans) on a hand-written
span buffer, and ``trace_launches_missing`` against the recorded trace
(``data/recorded_trace.json``), whose one Mosaic launch is counted up to a
window's; and the three span readers through the whole harness on a toy
table, asked for on the CPU (a rehearsal's line leaves out every metric but
``round_loop_compiles``: ``test_cli.py``)."""

import importlib
import json
import os

import pytest

import run as harness
from conftest import BENCH, ROOT
from layer_metrics import (hist_passes_run_per_tree, hist_slot_fill_pct,
                           hist_slots_per_tree, trace_launches_missing)
from lib import spantree, trace

NEW = ("hist_passes_run_per_tree", "hist_slots_per_tree",
       "hist_slot_fill_pct", "trace_launches_missing")
READERS = (hist_passes_run_per_tree, hist_slots_per_tree, hist_slot_fill_pct,
           trace_launches_missing)
ROWS = 68321280
DOC = json.load(open(os.path.join(BENCH, "tests", "data",
                                  "recorded_trace.json")))
# a 2-tree fit of the int8 cells (7 passes a tree at 1, 4, 4, 4, 8, 8, 8
# slots, 31 live positions) and of the float one (1, 4, 4, 8, 16, 16, 16; 61)
INT8 = dict(passes=14, launches=14, slots=74, live=62, iterations=2)
FLOAT = dict(passes=14, launches=14, slots=130, live=122, iterations=2)


def _buffer(monkeypatch, told, fits=4):
    """A warm-up fit and ``fits - 1`` window fits, each telling ``told``."""
    evs = [{"name": "gbdt_fit", "ph": "X", "ts": 1e6 * i, "dur": 9e5,
            "args": dict(told, path="fused")} for i in range(fits)]
    monkeypatch.setattr(spantree, "events", lambda: evs)


def _ctx(launches, fits=3, devices=1):
    """A traced run's reader context: the recorded trace's operations with
    its Mosaic kernel launched ``launches`` times."""
    ops = dict(trace.reduce_events(DOC)["ops"])
    kernel, = [n for n in ops if "tpu_custom_call" in n
               and f",{ROWS}]" in n]
    ops[kernel] = (ops[kernel][0], launches)
    assert trace.mosaic_kernels(ops, ROWS)[1] == launches
    return {"facts": {"attempted": fits, "trees": 2 * fits, "rows": ROWS},
            "trace": {"ops": ops, "devices": devices}}


@pytest.mark.parametrize("told,slots,fill", [(INT8, 37.0, 100 * 31 / 37),
                                             (FLOAT, 65.0, 100 * 61 / 65)])
def test_the_window_s_fits_are_read_a_tree(monkeypatch, told, slots, fill):
    _buffer(monkeypatch, told)
    ctx = _ctx(42)
    assert hist_passes_run_per_tree.read(ctx) == 7.0
    assert hist_slots_per_tree.read(ctx) == slots
    assert hist_slot_fill_pct.read(ctx) == pytest.approx(fill)
    assert trace_launches_missing.read(ctx) == 0


def test_a_trace_with_four_launches_cut_out_reads_four(monkeypatch):
    _buffer(monkeypatch, INT8)
    assert trace_launches_missing.read(_ctx(38)) == 4
    # the program's count does not come from the trace: it still reads 7.0
    assert hist_passes_run_per_tree.read(_ctx(38)) == 7.0


def test_every_device_launches_a_shard_s_count(monkeypatch):
    """Four chips, ``hist_blocks`` 2: a fit tells 28 launches a shard."""
    _buffer(monkeypatch, dict(INT8, launches=28))
    assert trace_launches_missing.read(_ctx(4 * 3 * 28, devices=4)) == 0
    assert trace_launches_missing.read(_ctx(4 * 3 * 28 - 1, devices=4)) == 1


def test_no_trace_reads_none_and_the_spans_still_read(monkeypatch):
    _buffer(monkeypatch, INT8)
    ctx = dict(_ctx(42), trace=None)
    assert trace_launches_missing.read(ctx) is None
    assert hist_slots_per_tree.read(ctx) == 37.0


@pytest.mark.parametrize("told", [dict(), dict(passes=14)],
                         ids=["a parent's spans", "half told"])
def test_a_program_without_the_attributes_reads_none(monkeypatch, told):
    _buffer(monkeypatch, told)
    got = [r.read(_ctx(42)) for r in READERS]
    assert got[1:] == [None] * 3
    assert got[0] == (7.0 if told else None)


def test_a_window_fit_that_does_not_tell_reads_none(monkeypatch):
    _buffer(monkeypatch, INT8)
    evs = spantree.events()
    evs[2] = dict(evs[2], args={"path": "fused"})
    assert [r.read(_ctx(42)) for r in READERS] == [None] * 4
    # no program, no buffer
    monkeypatch.setattr(spantree, "events", lambda: [])
    assert [r.read(_ctx(42)) for r in READERS] == [None] * 4


def test_every_new_entry_has_the_reader_it_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    cells = {w["name"] for w in bench["workloads"]}
    for name in NEW:
        entry, reader = entries[name], importlib.import_module(
            "layer_metrics." + name)
        assert (entry["unit"], entry["layer"], entry["moves"],
                entry["source"]) == (reader.UNIT, reader.LAYER, reader.MOVES,
                                     reader.SOURCE)
        # no list: every cell reports train_trees_per_s, so every cell
        assert "workloads" not in entry and len(cells) == 5
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves"}


@pytest.mark.parametrize("cell,toy", [
    ("criteo255q.train", "toy_limits.json"),
    ("criteo255f.train", "toy_limits_255f.json")])
def test_the_harness_reads_the_program_s_own_fits(cell, toy, monkeypatch,
                                                  capsys):
    """A traced rehearsal on the toy table, the span readers asked for as
    ``test_train_f.py`` asks for ``float_sum_sites``: the window's fits tell
    whole passes of a root and rounds no wider than the cell's, and
    ``trace_launches_missing`` stays the chip's."""
    limits = json.load(open(os.path.join(BENCH, "tests", "data", toy)))
    real = harness.load_cell

    def toy_cell(name):
        spec = real(name)
        spec["workload"]["limits"] = limits[name]
        return spec

    monkeypatch.setattr(harness, "load_cell", toy_cell)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    for reader in READERS[:3]:
        monkeypatch.setattr(reader, "NEEDS_CHIP", False, raising=False)
    assert harness.main(["--workload", cell, "--seed", "11", "--seconds",
                         "0.1", "--trace", "1", "--rows",
                         str(limits["rows"])]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = {name: line["metrics"][name]["value"] for name in NEW[:3]}
    assert "trace_launches_missing" not in line["metrics"]
    passes = got["hist_passes_run_per_tree"]
    assert 2 <= passes <= 7
    widest = 16 if cell == "criteo255f.train" else 8
    assert passes <= got["hist_slots_per_tree"] <= 1 + (passes - 1) * widest
    assert 0 < got["hist_slot_fill_pct"] <= 100
