"""chip_smoke.py's two CPU-checkable promises: the ``--toy`` rehearsal drives
the whole control flow (kernel variants, three fits, predict, serve, the
1-vs-all-devices identity on the suite's 8 virtual devices) without ever
printing the contract's success line, and anything short of a TPU without
``--toy`` fails before doing work."""

import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_over)
    for name in ("MMLSPARK_TPU_PALLAS_INTERPRET",
                 "MMLSPARK_TPU_DISABLE_PALLAS_HIST",
                 "MMLSPARK_TPU_HIST_ENGINE"):
        if name not in env_over:
            env.pop(name, None)
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=1500)


def test_toy_rehearsal_passes_and_never_claims_the_chip():
    r = _run("--toy")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    for phase in ("kernel", "train", "predict", "serve", "several chips"):
        assert any(ln.startswith(f"-- {phase} passed") for ln in lines), phase
    # all 24 kernel variants, through the real kernel code (interpreted)
    kernels = [ln for ln in lines if ln.startswith("kernel ")]
    assert len(kernels) == 24
    # each says which build its one-hot tiles got: packed words for int8
    # under the fold (255 bins, W = 1 and 16) and at 63 bins, else a compare
    assert sum(" onehot=packed " in ln for ln in kernels) == 10
    assert sum(" onehot=compare " in ln for ln in kernels) == 14
    assert all("stats=int8" in ln for ln in kernels if "=packed " in ln)
    assert "byte-identical on 1 and 8 devices" in r.stdout
    last = lines[-1]
    assert last.startswith("REHEARSAL passed")
    summary = json.loads(last[last.index("{"):])
    assert "ok" not in summary and summary["claim"] is None
    assert summary["device"]["platform"] == "cpu"
    assert not any(ln.startswith('{"ok"') for ln in lines)


def test_result_line_has_exactly_the_keys_the_chip_check_reads():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)             # stdlib imports only
    line = smoke.result_line({"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 4, "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_without_toy_anything_but_a_tpu_fails_before_doing_work():
    r = _run()
    assert r.returncode != 0
    assert "needs platform 'tpu'" in r.stderr
    assert "== kernel ==" not in r.stdout and '"ok"' not in r.stdout


def test_refuses_to_start_with_an_engine_override():
    for name, val in (("MMLSPARK_TPU_HIST_ENGINE", "scatter"),
                      ("MMLSPARK_TPU_DISABLE_PALLAS_HIST", "1"),
                      ("MMLSPARK_TPU_PALLAS_INTERPRET", "1")):
        r = _run(**{name: val})
        assert r.returncode != 0 and name in r.stderr, (name, r.stderr[-500:])
        assert "platform:" not in r.stdout      # refused before touching jax
