"""The command end to end at toy size on the CPU, as the driver calls it."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
DEVICE_METRICS = {"hist_passes_per_tree", "hist_kernel_share_pct",
                  "hist_roofline", "train_mfu_pct", "device_idle_pct",
                  "peak_hbm_gib"}


def _run(cell, trace, env_extra=None, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *BENCHMARK["command"][1:], "--workload", cell,
         "--seed", str(2 ** 31 + 77), "--seconds", "1", "--trace",
         str(trace), "--rows", "16384", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(cell):
    proc = _run(cell, 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert set(line["metrics"]) == {"train_trees_per_s", "setup_s"}
    assert line["metrics"]["train_trees_per_s"]["unit"] == "trees/s"
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": None}
    assert line["attempted"] >= 1 and line["failed"] == 0
    limits = json.load(open(os.path.join(
        BENCH, "workloads", cell + ".json")))["limits"]
    assert list(line["compared"]) == list(limits)
    for name in ("fits_differ", "bounds_differ", "compiles_in_window",
                 "count_gap", "leaf_gap", "gain_loss"):
        assert line["compared"][name]["limit"] == limits[name]
    assert line["compared"]["fits_differ"]["value"] == 0
    assert line["compared"]["bounds_differ"]["value"] == 0
    assert line["compared"]["count_gap"]["value"] == 0
    # the numbers compared are the last lines of standard error too
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") for t in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_has_no_device_metric_on_a_cpu(cell):
    proc = _run(cell, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    assert set(line["metrics"]) == {"round_loop_compiles"}
    assert not DEVICE_METRICS & set(line["metrics"])
    assert line["metrics"]["round_loop_compiles"]["value"] == 0
    assert "busy_s" not in line["device"]


def test_no_accelerator_no_numbers():
    # jax on its default platform finds no TPU here: only an explicit
    # JAX_PLATFORMS=cpu makes a rehearsal
    proc = _run(CELLS[0], 0, env_extra={"JAX_PLATFORMS": ""})
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_unknown_cell_is_refused():
    proc = _run("no.such.cell", 0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_layer_metric_files_agree_with_benchmark_json():
    import importlib
    for m in BENCHMARK["per_layer"]:
        mod = importlib.import_module("layer_metrics." + m["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"]), m["name"]
        assert callable(mod.read)


def test_every_cell_has_its_files():
    for w in BENCHMARK["workloads"]:
        wl = json.load(open(os.path.join(BENCH, "workloads",
                                         w["name"] + ".json")))
        assert wl["config"] == w["config"]
        assert os.path.isfile(os.path.join(BENCH, "lib",
                                           wl["kind"] + ".py"))
    for c in BENCHMARK["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in ("deployment", "assumed", "changed", "stats_dtype"):
            assert key in cfg
