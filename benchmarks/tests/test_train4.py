"""The four-chip cell rehearsed on four host devices: a toy table through the
whole harness ends ``correct``; the lower-precision control does not; and with
one shard's histogram left out of the ``psum`` (``faults_dp.shard_dropped``)
the comparison fails on the counts. Every drive is a process of its own,
because the device count is fixed when jax starts. Limits: the toy's own
(``data/toy_limits_train4.json``), as ``test_correct.py`` has them."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "criteo255q.train4"
TOY_FILE = os.path.join(BENCH, "tests", "data", "toy_limits_train4.json")


def _child(fault: str, extra) -> int:
    """In the child: plant, swap the limits for the toy's, run the harness."""
    import faults_dp
    import run as harness
    with open(TOY_FILE) as f:
        toy = json.load(f)
    real = harness.load_cell

    def toy_cell(name):
        spec = real(name)
        spec["workload"]["limits"] = toy[name]
        return spec

    harness.load_cell = toy_cell
    if fault:
        faults_dp.plant(fault)
    return harness.main(["--workload", CELL, "--seed", "11", "--seconds",
                         "0.1", "--trace", "0", "--rows", str(toy["rows"]),
                         *extra])


def _drive(fault="", extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, BENCH,
                                           os.path.join(BENCH, "tests")]))
    done = subprocess.run([sys.executable, os.path.abspath(__file__),
                           fault or "-", *extra], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=1200)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_control_is_not():
    sound = _drive()
    assert sound["correct"] is True, sound["compared"]
    assert sound["device"]["count"] == 4
    control = _drive(extra=("--control", "1"))
    assert control["correct"] is False
    noise = control["compared"]["leaf_noise"]
    assert noise["value"] > noise["limit"] >= \
        sound["compared"]["leaf_noise"]["value"]


def test_dropped_shard_is_not_correct():
    line = _drive(fault="shard_dropped")
    assert line["correct"] is False
    failed = {k for k, row in line["compared"].items()
              if row["value"] is None or row["value"] > row["limit"]}
    assert failed & {"count_gap", "leaf_noise"}, line["compared"]
    row = line["compared"]["count_gap"]
    assert row["value"] > 3 * row["limit"]


def test_coarser_program_levels_fail_leaf_noise():
    """The replay takes its noise unit from the configuration's levels, not
    from the program: a program that quantizes to a quarter of them reads
    four times the sound run's noise (1.78 against 0.45 here), where a unit
    that followed the program would still read the sound run's."""
    line = _drive(fault="coarse_levels")
    assert line["correct"] is False
    failed = {k for k, row in line["compared"].items()
              if row["value"] is None or row["value"] > row["limit"]}
    assert "leaf_noise" in failed, line["compared"]


def test_allreduce_reader_multiplies_the_staged_bytes_by_the_passes():
    from layer_metrics import allreduce_mib_per_tree as reader
    kernel = ('%k = s32[39,48,256] custom-call(u8[39,1024] %x), '
              'custom_call_target="tpu_custom_call"')
    ctx = {"facts": {"rows": 1024, "trees": 2, "allreduce_bytes": {
               "tree": 2 ** 20, "round": 2 ** 21, "level": 0}},
           "trace": {"devices": 4, "ops": {kernel: [1.0, 2 * 4 * 7]}}}
    assert reader.read(ctx) == pytest.approx(1 + 2 * 6)
    ctx["facts"]["allreduce_bytes"] = {"tree": 0, "round": 0, "level": 0}
    assert reader.read(ctx) is None               # the parent's program
    del ctx["facts"]["allreduce_bytes"]
    assert reader.read(ctx) is None


def test_collective_reader_matches_the_opcode():
    """XLA names an uncombined reduction after the jax primitive; the reader
    goes by the opcode, and a consumer of a collective is not one."""
    from layer_metrics import collective_exposed_pct as reader
    ops = {
        "%psum.21 = f32[39,48,255]{2,1,0:T(8,128)S(1)} all-reduce("
        "%broadcast_multiply_fusion.2), channel_id=1": [0.002, 48],
        "%all-reduce.42 = (f32[39,3,255]{2,1,0}, f32[3]{0}) all-reduce("
        "f32[39,3,255]{2,1,0} %fusion.2361, f32[3]{0} %copy)": [0.001, 8],
        "%all-gather-done.1 = f32[8]{0} all-gather-done("
        "%all-gather-start.1)": [0.001, 8],
        "%fusion.3 = f32[8]{0} fusion(f32[8] %all-reduce.3), "
        "kind=kLoop": [5.0, 8],
        "%gbdt_node_hist_kernel.7 = s32[39,48,256]{2,1,0} custom-call("
        "u8[39,1024] %psum.3)": [7.0, 8],
    }
    ctx = {"trace": {"devices": 4, "ops": ops}, "window_s": 1.0}
    assert reader.read(ctx) == pytest.approx(100.0 * 0.004 / 4.0)
    ctx["trace"]["ops"] = {k: v for k, v in ops.items() if v[0] > 1}
    assert reader.read(ctx) is None


def test_parent_has_no_such_cell():
    """A checkout without the cell ends at once, and says why."""
    import run as harness
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell("criteo255q.train5")


if __name__ == "__main__":
    sys.exit(_child("" if sys.argv[1] == "-" else sys.argv[1], sys.argv[2:]))
