#!/usr/bin/env bash
# Standalone static-analysis lane (no pytest, no jax): graftlint over
# the whole tree with machine-readable output, plus the env-var docs
# drift gate and a seeded-chaos smoke (a live fault-injected serving
# round-trip proving the failpoint plane fires, recovers, and replays
# deterministically). Exit nonzero on any unsuppressed finding,
# drifted table, or chaos-smoke failure.
#
#   tools/ci_check.sh            # human summary + JSON artifact
#   GRAFTLINT_JSON=out.json tools/ci_check.sh
#   CI_SKIP_CHAOS=1 tools/ci_check.sh      # skip the chaos smoke
#   CI_SKIP_ASYNC=1 tools/ci_check.sh      # skip the async-serving smoke
#   CI_SKIP_MULTICHIP=1 tools/ci_check.sh  # skip the 8-device dry run
#   CI_SKIP_BUNDLE=1 tools/ci_check.sh     # skip the AOT-bundle smoke
#   CI_SKIP_QUANT=1 tools/ci_check.sh      # skip the int8 quantized smoke
#   CI_SKIP_ROOFLINE=1 tools/ci_check.sh   # skip the introspection smoke
#   CI_SKIP_SLO=1 tools/ci_check.sh        # skip the SLO-breach smoke
#   CI_SKIP_TUNING=1 tools/ci_check.sh     # skip the auto-tuner smoke
#   CI_SKIP_POSTMORTEM=1 tools/ci_check.sh # skip the post-mortem smoke
set -u -o pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
JSON_OUT="${GRAFTLINT_JSON:-}"

rc=0

if [ -n "$JSON_OUT" ]; then
    if ! (cd "$ROOT" && python -m tools.graftlint --json > "$JSON_OUT"); then
        rc=1
    fi
    # a crash/usage error (exit 2) leaves no JSON — don't traceback on it
    if [ -s "$JSON_OUT" ]; then
        n=$(python - "$JSON_OUT" <<'EOF'
import json, sys
print(len(json.load(open(sys.argv[1]))["findings"]))
EOF
)
        echo "graftlint: $n finding(s) -> $JSON_OUT"
    else
        echo "graftlint: no JSON produced (crash or usage error)" >&2
    fi
else
    (cd "$ROOT" && python -m tools.graftlint) || rc=1
fi

(cd "$ROOT" && python tools/gen_env_docs.py --check) || rc=1

if [ "${CI_SKIP_CHAOS:-0}" != "1" ]; then
    if (cd "$ROOT" && python - <<'EOF'
import json
import urllib.error
import urllib.request

from mmlspark_tpu.io.serving import serve
from mmlspark_tpu.observability import flight, metrics
from mmlspark_tpu.robustness import failpoints

metrics.set_enabled(True)

# deterministic replay: the same spec + seed draws the same pattern
def pattern(seed):
    failpoints.configure("http.send:error_503:0.5", seed=seed)
    out = [failpoints.fault_point("http.send") is not None
           for _ in range(32)]
    failpoints.clear()
    return out

assert pattern(11) == pattern(11), "seeded chaos did not replay"

# live smoke: one injected 503 at admission, then clean recovery.
# Pinned to the deprecated threaded engine on purpose — the async lane
# below covers the default engine, and the threaded stack keeps chaos
# coverage until it is retired.
failpoints.configure("serving.handle:error_503@1", seed=11)
q = (serve().address("localhost", 0, "ci_chaos").batch(8, 5)
     .engine("threaded")
     .transform(lambda ds: ds.with_column("reply", [
         {"entity": {"i": v["i"]}, "statusCode": 200}
         for v in ds["value"]])).start())
try:
    def post(payload):
        req = urllib.request.Request(
            q.server.url, data=json.dumps(payload).encode(), method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    status, _ = post({"i": 0})
    assert status == 503, f"injected fault not served: {status}"
    status, body = post({"i": 1})
    assert status == 200 and json.loads(body) == {"i": 1}, \
        f"recovery failed: {status} {body!r}"
finally:
    q.stop()

assert metrics.counter("failpoints_fired_total", site="serving.handle",
                       kind="error_503").value == 1.0
assert any(e["kind"] == "failpoint" and e["site"] == "serving.handle"
           for e in flight.events()), "fault missing from the flight ring"
print("chaos smoke: injected 503 served, recovery clean, replay deterministic")
EOF
    ); then
        :
    else
        echo "ci_check: chaos smoke FAILED" >&2
        rc=1
    fi
fi

# async-serving smoke lane: a live round-trip on the io/aserve engine
# (continuous batching + keep-alive front) plus an injected-503 chaos
# replay — the same proof the chaos lane gives the threaded engine, on
# the async plane, without pytest.
if [ "${CI_SKIP_ASYNC:-0}" != "1" ]; then
    if (cd "$ROOT" && python - <<'EOF'
import json
import urllib.error
import urllib.request

from mmlspark_tpu.io.aserve import AsyncServingQuery
from mmlspark_tpu.io.serving import serve
from mmlspark_tpu.observability import flight, metrics
from mmlspark_tpu.robustness import failpoints

metrics.set_enabled(True)

# deterministic replay (batch-side site, so the live smoke's
# serving.handle counter below stays exactly 1)
def pattern(seed):
    failpoints.configure("serving.batch:error_503:0.5", seed=seed)
    out = [failpoints.fault_point("serving.batch") is not None
           for _ in range(32)]
    failpoints.clear()
    return out

assert pattern(23) == pattern(23), "seeded chaos did not replay"

failpoints.configure("serving.handle:error_503@2", seed=23)
q = (serve().address("localhost", 0, "ci_async").engine("async")
     .transform(lambda ds: ds.with_column("reply", [
         {"entity": {"i": v["i"]}, "statusCode": 200}
         for v in ds["value"]])).start())
assert isinstance(q, AsyncServingQuery), type(q)
try:
    def post(payload):
        req = urllib.request.Request(
            q.server.url, data=json.dumps(payload).encode(), method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    status, body = post({"i": 0})
    assert status == 200 and json.loads(body) == {"i": 0}, \
        f"async round-trip failed: {status} {body!r}"
    status, _ = post({"i": 1})
    assert status == 503, f"injected fault not served: {status}"
    status, body = post({"i": 2})
    assert status == 200 and json.loads(body) == {"i": 2}, \
        f"recovery failed: {status} {body!r}"
finally:
    q.stop()

assert metrics.counter("failpoints_fired_total", site="serving.handle",
                       kind="error_503").value == 1.0
assert any(e["kind"] == "failpoint" and e["site"] == "serving.handle"
           for e in flight.events()), "fault missing from the flight ring"
print("async smoke: round-trip clean, injected 503 served, recovery "
      "clean, replay deterministic")
EOF
    ); then
        :
    else
        echo "ci_check: async-serving smoke FAILED" >&2
        rc=1
    fi
fi

# bundle smoke lane: build an AOT serving bundle in one process, warm-start
# a real serving_main worker from it in another, and assert the ROADMAP
# item 4 acceptance end to end — /healthz flips ready, the first /predict
# answers, and the flight ring holds ZERO compile events.
if [ "${CI_SKIP_BUNDLE:-0}" != "1" ]; then
    if (cd "$ROOT" && env JAX_PLATFORMS=cpu \
            python - <<'EOF'
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from mmlspark_tpu.models.gbdt.booster import train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig

env = dict(os.environ, JAX_PLATFORMS="cpu")
with tempfile.TemporaryDirectory() as d:
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    booster = train_booster(X=X, y=y, num_iterations=3, objective="binary",
                            cfg=GrowConfig(num_leaves=7, min_data_in_leaf=5))
    model = os.path.join(d, "model.txt")
    with open(model, "w") as f:
        f.write(booster.model_string())

    # process 1: offline bundle build via the CLI
    bundle = os.path.join(d, "model.bundle")
    subprocess.run([sys.executable, "-m", "mmlspark_tpu.bundles", "build",
                    "--model", model, "--out", bundle, "--max-batch", "8"],
                   env=env, check=True, timeout=300)
    assert os.path.exists(os.path.join(bundle, "MANIFEST.json"))

    # process 2: warm-start a worker from the bundle
    p = subprocess.Popen(
        [sys.executable, "-m", "mmlspark_tpu.io.serving_main", "worker",
         "--model", model, "--registry", os.path.join(d, "reg"),
         "--host", "localhost", "--port", "0", "--max-batch", "8",
         "--bundle", bundle],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    try:
        line = p.stdout.readline()
        m = re.search(r"serving on \S+:(\d+)", line)
        assert m, f"no ready-line: {line!r}"
        port = int(m.group(1))
        # readiness flip: poll /healthz until green
        deadline = time.monotonic() + 60
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://localhost:{port}/healthz", timeout=5) as r:
                    hz = json.loads(r.read())
                if hz.get("ready"):
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline, "worker never became ready"
            time.sleep(0.05)
        body = json.dumps({"features": [0.1] * 6}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                f"http://localhost:{port}/serving", data=body,
                method="POST"), timeout=10) as r:
            reply = json.loads(r.read())
            assert r.status == 200 and "prediction" in reply, reply
        with urllib.request.urlopen(
                f"http://localhost:{port}/debug/flight", timeout=5) as r:
            ring = json.loads(r.read())
        compiles = [e for e in ring["events"] if e.get("kind") == "compile"]
        assert compiles == [], f"warm start compiled: {compiles}"
        loaded = [e for e in ring["events"] if e.get("kind") == "bundle"
                  and e.get("event") == "entry_loaded"]
        assert loaded, "no bundle entries loaded"
    finally:
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=30)
print("bundle smoke: built offline, warm-started ready, first predict "
      "with zero compile events")
EOF
    ); then
        :
    else
        echo "ci_check: bundle smoke FAILED" >&2
        rc=1
    fi
fi

# quantized smoke lane: the int8 end-to-end story in two processes —
# offline build of a bundle carrying the int8 predict lane (from the
# .npz native model, the format that keeps the binner grid), then a
# worker pinned to MMLSPARK_TPU_PREDICT_DTYPE=int8 warm-starts from it
# on the async rows path: /varz shows the pinned lane, the first
# /predict answers, and the flight ring holds ZERO compile events.
if [ "${CI_SKIP_QUANT:-0}" != "1" ]; then
    if (cd "$ROOT" && env JAX_PLATFORMS=cpu \
            python - <<'EOF'
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from mmlspark_tpu.models.gbdt.booster import train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig

env = dict(os.environ, JAX_PLATFORMS="cpu",
           MMLSPARK_TPU_PREDICT_DTYPE="int8")
with tempfile.TemporaryDirectory() as d:
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    booster = train_booster(X=X, y=y, num_iterations=3, objective="binary",
                            cfg=GrowConfig(num_leaves=7, min_data_in_leaf=5))
    model = os.path.join(d, "model.npz")
    booster.save(model)

    # process 1: offline bundle build carrying the int8 lane
    bundle = os.path.join(d, "model.bundle")
    subprocess.run([sys.executable, "-m", "mmlspark_tpu.bundles", "build",
                    "--model", model, "--out", bundle, "--max-batch", "8",
                    "--predict-dtypes", "f32,int8"],
                   env=env, check=True, timeout=300)
    manifest = json.load(open(os.path.join(bundle, "MANIFEST.json")))
    lanes = {e.get("predict_dtype") for e in manifest["entries"]}
    assert "int8" in lanes, f"int8 lane missing from bundle: {lanes}"

    # process 2: warm-start an int8-pinned async worker from the bundle
    p = subprocess.Popen(
        [sys.executable, "-m", "mmlspark_tpu.io.serving_main", "worker",
         "--model", model, "--registry", os.path.join(d, "reg"),
         "--host", "localhost", "--port", "0", "--max-batch", "8",
         "--engine", "async", "--bundle", bundle],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    try:
        line = p.stdout.readline()
        m = re.search(r"serving on \S+:(\d+)", line)
        assert m, f"no ready-line: {line!r}"
        port = int(m.group(1))
        deadline = time.monotonic() + 60
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://localhost:{port}/healthz", timeout=5) as r:
                    hz = json.loads(r.read())
                if hz.get("ready"):
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline, "worker never became ready"
            time.sleep(0.05)
        with urllib.request.urlopen(
                f"http://localhost:{port}/varz", timeout=5) as r:
            varz = json.loads(r.read())
        pinned = (varz.get("config") or {}).get("predict_dtype")
        assert pinned == "int8", f"/varz predict_dtype: {pinned!r}"
        body = json.dumps({"features": [0.1] * 6}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                f"http://localhost:{port}/serving", data=body,
                method="POST"), timeout=10) as r:
            reply = json.loads(r.read())
            assert r.status == 200 and "prediction" in reply, reply
        with urllib.request.urlopen(
                f"http://localhost:{port}/debug/flight", timeout=5) as r:
            ring = json.loads(r.read())
        compiles = [e for e in ring["events"] if e.get("kind") == "compile"]
        assert compiles == [], f"int8 warm start compiled: {compiles}"
    finally:
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=30)
print("quantized smoke: int8 bundle built, int8-pinned worker "
      "warm-started (predict_dtype on /varz), first predict with zero "
      "compile events")
EOF
    ); then
        :
    else
        echo "ci_check: quantized smoke FAILED" >&2
        rc=1
    fi
fi

# introspection smoke lane: boot a live serving_main worker, score one
# request, and assert the performance-introspection plane closed the loop
# — /debug/roofline names the fused predict executable with at least one
# observed call (plus explicit peaks provenance: a table/env match on
# TPU, "unknown" off-TPU), and the per-request stage histograms
# (admission/forming_wait/score/write) are non-empty on /metrics.
if [ "${CI_SKIP_ROOFLINE:-0}" != "1" ]; then
    if (cd "$ROOT" && env JAX_PLATFORMS=cpu \
            python - <<'EOF'
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from mmlspark_tpu.models.gbdt.booster import train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig

env = dict(os.environ, JAX_PLATFORMS="cpu")
with tempfile.TemporaryDirectory() as d:
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    booster = train_booster(X=X, y=y, num_iterations=3, objective="binary",
                            cfg=GrowConfig(num_leaves=7, min_data_in_leaf=5))
    model = os.path.join(d, "model.txt")
    with open(model, "w") as f:
        f.write(booster.model_string())

    p = subprocess.Popen(
        [sys.executable, "-m", "mmlspark_tpu.io.serving_main", "worker",
         "--model", model, "--registry", os.path.join(d, "reg"),
         "--host", "localhost", "--port", "0", "--max-batch", "8"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    try:
        line = p.stdout.readline()
        m = re.search(r"serving on \S+:(\d+)", line)
        assert m, f"no ready-line: {line!r}"
        port = int(m.group(1))
        deadline = time.monotonic() + 60
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://localhost:{port}/healthz", timeout=5) as r:
                    hz = json.loads(r.read())
                if hz.get("ready"):
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline, "worker never became ready"
            time.sleep(0.05)
        body = json.dumps({"features": [0.1] * 6}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                f"http://localhost:{port}/serving", data=body,
                method="POST"), timeout=30) as r:
            reply = json.loads(r.read())
            assert r.status == 200 and "prediction" in reply, reply
        with urllib.request.urlopen(
                f"http://localhost:{port}/debug/roofline", timeout=5) as r:
            roof = json.loads(r.read())
        src = (roof.get("peaks") or {}).get("source")
        assert src, f"no peaks provenance: {roof.get('peaks')}"
        called = [e for e in roof.get("executables", [])
                  if e.get("kind") == "predict" and (e.get("calls") or 0) >= 1]
        assert called, f"no called predict executable: {roof}"
        with urllib.request.urlopen(
                f"http://localhost:{port}/metrics", timeout=5) as r:
            metrics_text = r.read().decode()
        assert 'serving_stage_seconds' in metrics_text, \
            "stage histograms missing from /metrics"
        stages = set(re.findall(
            r'serving_stage_seconds_count\{[^}]*stage="([a-z_]+)"',
            metrics_text))
        assert {"admission", "forming_wait", "score",
                "write"} <= stages, f"incomplete stage set: {stages}"
    finally:
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=30)
print(f"roofline smoke: predict executable observed "
      f"(peaks={src}, flops={'yes' if called[0].get('flops') else 'no'}), "
      f"stage histograms complete")
EOF
    ); then
        :
    else
        echo "ci_check: roofline smoke FAILED" >&2
        rc=1
    fi
fi

# SLO smoke lane: boot a live serving_main worker with a deliberately
# tight objective (every request breaches p99<0.01ms), drive traffic past
# it, and assert the SLO plane closed the loop — the slo_burn_rate gauge
# trips past 1.0, /debug/slo reports the breach, and /debug/tail holds at
# least one sampled stage timeline naming the dominant stage.
if [ "${CI_SKIP_SLO:-0}" != "1" ]; then
    if (cd "$ROOT" && env JAX_PLATFORMS=cpu \
            MMLSPARK_TPU_SLO="serving:p99<0.01ms,err<1%" \
            python - <<'EOF'
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from mmlspark_tpu.models.gbdt.booster import train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig

env = dict(os.environ, JAX_PLATFORMS="cpu",
           MMLSPARK_TPU_SLO="serving:p99<0.01ms,err<1%")
with tempfile.TemporaryDirectory() as d:
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    booster = train_booster(X=X, y=y, num_iterations=3, objective="binary",
                            cfg=GrowConfig(num_leaves=7, min_data_in_leaf=5))
    model = os.path.join(d, "model.txt")
    with open(model, "w") as f:
        f.write(booster.model_string())

    p = subprocess.Popen(
        [sys.executable, "-m", "mmlspark_tpu.io.serving_main", "worker",
         "--model", model, "--registry", os.path.join(d, "reg"),
         "--host", "localhost", "--port", "0", "--max-batch", "8"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    try:
        line = p.stdout.readline()
        m = re.search(r"serving on \S+:(\d+)", line)
        assert m, f"no ready-line: {line!r}"
        port = int(m.group(1))
        deadline = time.monotonic() + 60
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://localhost:{port}/healthz", timeout=5) as r:
                    hz = json.loads(r.read())
                if hz.get("ready"):
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline, "worker never became ready"
            time.sleep(0.05)
        body = json.dumps({"features": [0.1] * 6}).encode()
        for _ in range(10):
            with urllib.request.urlopen(urllib.request.Request(
                    f"http://localhost:{port}/serving", data=body,
                    method="POST"), timeout=30) as r:
                assert r.status == 200, r.status
        with urllib.request.urlopen(
                f"http://localhost:{port}/debug/slo", timeout=5) as r:
            slo = json.loads(r.read())
        ep = (slo.get("endpoints") or {}).get("serving")
        assert ep, f"no 'serving' endpoint in /debug/slo: {slo}"
        fast = ep["windows"]["fast5m"]
        assert ep["breaching"] and fast["burn_rate"] > 1.0, ep
        with urllib.request.urlopen(
                f"http://localhost:{port}/metrics", timeout=5) as r:
            metrics_text = r.read().decode()
        burns = [float(v) for v in re.findall(
            r'slo_burn_rate\{[^}]*window="fast5m"[^}]*\} (\S+)',
            metrics_text)]
        assert burns and max(burns) > 1.0, \
            f"slo_burn_rate gauge never tripped: {burns}"
        with urllib.request.urlopen(
                f"http://localhost:{port}/debug/tail", timeout=5) as r:
            tail = json.loads(r.read())
        timed = [s for s in tail.get("samples", []) if s.get("stages")]
        assert timed, f"no sampled stage timelines: {tail}"
        dom = tail["attribution"]["dominant_stage"]
        assert dom in ("admission", "forming_wait", "score", "write"), dom
    finally:
        p.send_signal(signal.SIGTERM)
        p.wait(timeout=30)
print(f"SLO smoke: burn_rate={max(burns):.1f} (>1), "
      f"{len(timed)} sampled timeline(s), dominant stage {dom}")
EOF
    ); then
        :
    else
        echo "ci_check: SLO smoke FAILED" >&2
        rc=1
    fi
fi

# tuning smoke lane: the measure→decide loop across two processes — the
# first process observes a serving batch-size histogram, decides the
# predict bucket ladder at the evidence bar and persists it to a shared
# store; the second process warm-starts the same knob from the store
# (source=store) without deciding again, and the snapshot (/debug/tuning's
# payload) reports the decision with its evidence.
if [ "${CI_SKIP_TUNING:-0}" != "1" ]; then
    if (cd "$ROOT" && env JAX_PLATFORMS=cpu \
            python - <<'EOF'
import json
import os
import subprocess
import sys
import tempfile

SNIPPET = r'''
import json
import sys
from mmlspark_tpu.observability import flight
from mmlspark_tpu import tuning

if sys.argv[1] == "observe":
    for n in (3, 5, 37, 37, 100) * 8:
        tuning.observe_batch_size(n)
    tuning.flush()
ladder = tuning.resolve_bucket_ladder()
dec = [(e["choice"], e["source"]) for e in flight.events()
       if e.get("kind") == "tuning" and e.get("site") == "bucket_ladder"]
print(json.dumps({"ladder": ladder, "decisions": dec,
                  "snapshot": tuning.snapshot_payload()}))
'''

with tempfile.TemporaryDirectory() as d:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MMLSPARK_TPU_TUNING_DIR=d,
               MMLSPARK_TPU_TUNE_MIN_SAMPLES="16")

    def run(mode):
        p = subprocess.run([sys.executable, "-c", SNIPPET, mode], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.splitlines()[-1])

    first = run("observe")
    assert first["ladder"] == [1, 2, 4, 8, 40, 104], first
    assert first["decisions"] and all(
        src == "measured" for _c, src in first["decisions"]), first
    assert os.path.exists(os.path.join(d, "tuning.json")), os.listdir(d)

    second = run("serve")
    assert second["ladder"] == first["ladder"], (first, second)
    assert second["decisions"] and all(
        src == "store" for _c, src in second["decisions"]), second
    snap = second["snapshot"]
    assert snap["decisions"]["bucket_ladder"].get("evidence"), snap
print("tuning smoke: first process decided the bucket ladder and persisted "
      "it, second process warm-started from the store")
EOF
    ); then
        :
    else
        echo "ci_check: tuning smoke FAILED" >&2
        rc=1
    fi
fi

# postmortem smoke lane: the fleet black-box story end to end, in real
# processes — a gateway with fast federation sweeps pulls an echo
# worker's flight ring into the fleet timeline, fault injection lands at
# least one 503, then the worker is SIGKILLed (no drain, no dump of its
# own) and tools/postmortem.py runs against what's left: the report must
# name the dead worker and carry its pre-kill flight events, recovered
# from the gateway timeline alone.
if [ "${CI_SKIP_POSTMORTEM:-0}" != "1" ]; then
    if (cd "$ROOT" && env JAX_PLATFORMS=cpu \
            python - <<'EOF'
import json
import os
import re
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

ROOT = os.getcwd()
TRACE_ID = "f" * 32
TRACEPARENT = f"00-{TRACE_ID}-{'b' * 16}-01"


def wait_line(proc, pattern, timeout=120):
    deadline = time.monotonic() + timeout
    seen = []
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            time.sleep(0.05)
            continue
        seen.append(line)
        m = re.search(pattern, line)
        if m:
            return m
    raise AssertionError(
        f"no {pattern!r} from child: {''.join(seen)[-2000:]}")


def request(host, port, path, body=None, headers=None):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}",
        data=body.encode() if body else None, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


with tempfile.TemporaryDirectory() as d:
    registry = os.path.join(d, "registry")
    flight_dir = os.path.join(d, "flight")
    out_dir = os.path.join(d, "pm")
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               MMLSPARK_TPU_FLIGHT_DIR=flight_dir,
               MMLSPARK_TPU_FEDERATION_INTERVAL_SECONDS="0.2")
    env.pop("MMLSPARK_TPU_FAILPOINTS", None)
    env.pop("MMLSPARK_TPU_FAILPOINTS_SEED", None)
    genv = dict(env, MMLSPARK_TPU_FAILPOINTS="gateway.route:error_503:0.2",
                MMLSPARK_TPU_FAILPOINTS_SEED="5")
    worker = subprocess.Popen(
        [sys.executable, "-m", "tests._chaos_worker",
         "--registry", registry],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    gateway = None
    try:
        m = wait_line(worker, r"worker \w+ serving on ([\w.]+):(\d+)")
        wlabel = f"localhost:{m.group(2)}"
        gateway = subprocess.Popen(
            [sys.executable, "-m", "mmlspark_tpu.io.serving_main",
             "gateway", "--registry", registry,
             "--host", "localhost", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=genv)
        m = wait_line(gateway, r"gateway on ([\w.]+):(\d+)")
        host, port = m.group(1), int(m.group(2))

        statuses = []
        for k in range(40):
            st, _ = request(host, port, "/serving",
                            json.dumps({"i": k}),
                            {"traceparent": TRACEPARENT})
            statuses.append(st)
        assert statuses.count(200) >= 1, statuses
        # the injected 503s fire at the gateway.route fault site and are
        # absorbed by retry/failover — the client sees 200s, the flight
        # ring sees the faults
        st, body = request(host, port, "/debug/flight")
        assert st == 200 and any(
            e.get("kind") == "failpoint"
            for e in json.loads(body)["events"]), body[:500]

        # the sweep must pull the worker's ring before the kill
        deadline = time.monotonic() + 60
        cursors = {}
        while time.monotonic() < deadline:
            st, body = request(host, port, "/debug/timeline")
            assert st == 200, body[:500]
            cursors = json.loads(body).get("cursors") or {}
            if cursors.get(wlabel, 0) > 0:
                break
            time.sleep(0.2)
        assert cursors.get(wlabel, 0) > 0, cursors

        worker.kill()                    # SIGKILL: no drain, no dump
        worker.wait(timeout=30)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _st, body = request(host, port, "/debug/timeline")
            kinds = {e.get("kind")
                     for e in json.loads(body).get("events") or []}
            if "worker_scrape_dead" in kinds:
                break
            time.sleep(0.2)
        assert "worker_scrape_dead" in kinds, sorted(kinds)

        pm = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "postmortem.py"),
             "--gateway", f"{host}:{port}", "--flight-dir", flight_dir,
             "--out", out_dir],
            capture_output=True, text=True, timeout=300, env=env)
        assert pm.returncode == 0, pm.stderr[-2000:]
        with open(os.path.join(out_dir, "report.txt")) as f:
            report = f.read()
        assert f"Implicated worker: {wlabel}" in report, report
        assert "DEAD at collection" in report, report
        # pre-kill flight events recovered from the fleet timeline
        assert "serving_request" in report, report
        assert "worker_scrape_dead" in report, report
    finally:
        for p in (worker, gateway):
            if p is not None:
                p.terminate()
        if gateway is not None:
            gateway.wait(timeout=30)
print("postmortem smoke: SIGKILLed worker named with its pre-kill "
      "flight events, from the gateway timeline + dumps alone")
EOF
    ); then
        :
    else
        echo "ci_check: postmortem smoke FAILED" >&2
        rc=1
    fi
fi

# dryrun_multichip lane: the cross-device-count tree-identity suite on a
# virtual 8-device CPU mesh (xla_force_host_platform_device_count) — the
# full histogram-engine matrix, including the tiers tier-1 deselects as
# `slow`. Proves every engine grows bit-identical trees on 1/2/8 devices
# before any real-pod run trusts the sharded path.
if [ "${CI_SKIP_MULTICHIP:-0}" != "1" ]; then
    if (cd "$ROOT" && env JAX_PLATFORMS=cpu \
            XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python -m pytest tests/test_placement.py -q \
            -p no:cacheprovider); then
        echo "ci_check: dryrun_multichip clean"
    else
        echo "ci_check: dryrun_multichip FAILED" >&2
        rc=1
    fi
fi

if [ "$rc" -ne 0 ]; then
    echo "ci_check: FAILED (graftlint findings, env-docs drift, chaos/async/bundle/roofline/SLO/tuning/postmortem smoke, or multichip dry run)" >&2
else
    echo "ci_check: clean"
fi
exit "$rc"
