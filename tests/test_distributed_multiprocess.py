"""Real multi-process ``jax.distributed`` coverage (two local processes).

The reference tests its distributed rendezvous with real sockets (SURVEY §4
"no fake backend"; lightgbm/LightGBMUtils.scala:116-185). The analog here:
two OS processes + a localhost coordinator build one global 2-device CPU
mesh, cross the barrier, run a cross-process psum (Gloo collectives), and
fit a GBDT whose model must be bit-identical to a single-process
2-virtual-device run — proving the mesh abstraction makes process
boundaries invisible to the training code.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_dist_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _clean_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)        # workers get 1 real CPU device each
    return env


def _run_worker(args, env, timeout=240):
    return subprocess.run([sys.executable, WORKER, *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_two_process_init_psum_and_gbdt_fit(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    env = _clean_env()
    # p1's streams go to files, not PIPEs: nobody drains a PIPE while the
    # test blocks on p0, and >64 KiB of jax/Gloo logging would deadlock
    # p1 (and with it the barrier both workers wait at)
    p1_log = open(tmp_path / "p1.log", "w+")
    p1 = subprocess.Popen([sys.executable, WORKER, coord, "2", "1"],
                          env=env, stdout=p1_log, stderr=subprocess.STDOUT,
                          text=True)
    try:
        p0 = _run_worker([coord, "2", "0"], env)
        p1.wait(timeout=60)
    finally:
        if p1.poll() is None:
            p1.kill()
        p1_log.seek(0)
        err1 = p1_log.read()
        p1_log.close()
    assert p0.returncode == 0, f"proc0 failed:\n{p0.stderr[-3000:]}"
    assert p1.returncode == 0, f"proc1 failed:\n{err1[-3000:]}"

    dist = json.loads(p0.stdout.strip().splitlines()[-1])
    assert dist["process_count"] == 2
    assert dist["device_count"] == 2
    # psum over shards [0..3], [4..7] -> elementwise sum across processes
    assert dist["psum"] == [4.0, 6.0, 8.0, 10.0]
    assert dist["num_trees"] == 4

    # single-process reference on 2 virtual devices: same shard count, so
    # the same floating-point reduction tree -> bit-identical model
    ref = _run_worker(["single2"], env)
    assert ref.returncode == 0, f"reference failed:\n{ref.stderr[-3000:]}"
    ref_out = json.loads(ref.stdout.strip().splitlines()[-1])
    assert ref_out["process_count"] == 1
    assert dist["model_sha"] == ref_out["model_sha"], (
        "2-process model diverged from single-process 2-device model")
