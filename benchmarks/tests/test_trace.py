"""The trace reduction on a small recorded trace (``data/recorded_trace.json``:
one leafwise round of ``criteo255.train`` from a chip run of PR 25)."""

import json
import os

import pytest

from conftest import BENCH
from lib import trace

DOC = json.load(open(os.path.join(BENCH, "tests", "data",
                                  "recorded_trace.json")))


def test_reduction_of_the_recorded_round():
    red = trace.reduce_events(DOC)
    assert red["devices"] == 1
    # the %cond that spans the round is a container, not busy time
    assert not any(n.startswith("%cond.57") for n in red["ops"])
    assert red["busy_s"] == pytest.approx(4.571404687, rel=1e-9)
    assert red["span_s"] == pytest.approx(4.579554143, rel=1e-9)
    assert red["busy_s"] < red["span_s"]
    top = trace.top_ops(red["ops"], 3)
    assert top[0][0].startswith("%fusion.18 = u32[546570240]")
    assert top[0][1] == pytest.approx(3.873143165)
    assert top[1][0].startswith("%branch_1_fun.2 = f32[39,48,256]")
    assert red["ops"][top[2][0]][1] == 8            # launches are counted
    # gaps are named by the innermost host span open at their middle
    assert red["idle_gaps"][0][0] == "bench_fit"
    assert red["idle_gaps"][0][1] == pytest.approx(8.2028e-05)
    assert len(red["idle_gaps"]) == 10


def test_mosaic_kernel_is_found_by_target_and_operand():
    ops = trace.reduce_events(DOC)["ops"]
    assert trace.mosaic_kernels(ops, 68321280) == (
        pytest.approx(0.571838813), 1)
    assert trace.mosaic_kernels(ops, 1234) == (0.0, 0)


def test_leaves_and_union():
    events = [["while", 0.0, 100.0], ["a", 10.0, 20.0], ["b", 25.0, 10.0],
              ["cond", 40.0, 50.0], ["c", 45.0, 10.0], ["d", 60.0, 20.0]]
    doc = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events}]}]}
    red = trace.reduce_events(doc)
    assert sorted(red["ops"]) == ["a", "b", "c", "d"]
    # a and b overlap by 5 ns: the union counts them once
    assert red["busy_s"] == pytest.approx((25 + 10 + 20) * 1e-9)
    assert red["idle_gaps"][0] == ["no host span open",
                                   pytest.approx(10e-9)]
    assert trace.reduce_events({"planes": []})["devices"] == 0
