"""Deployment tooling: serving_main entrypoint + docker/helm tree.

The reference ships docker images and cluster tooling (tools/docker,
tools/helm). Their behavior here lives in `mmlspark_tpu.io.serving_main`,
which this suite runs FOR REAL (worker subprocess + gateway subprocess over
a shared file registry, requests through the gateway); the docker/helm files
are validated structurally (no docker daemon in CI).
"""

import http.client
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")


def _wait_for(proc, pattern, timeout=90):
    """Deadline-enforced wait for a line matching ``pattern`` (stdout is
    drained on a reader thread: a silent hang fails at the deadline instead
    of blocking the suite on readline)."""
    import queue
    import threading

    q: "queue.Queue[str]" = queue.Queue()

    def reader():
        for line in proc.stdout:
            q.put(line)

    threading.Thread(target=reader, daemon=True).start()
    out = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            line = q.get(timeout=0.25)
        except queue.Empty:
            continue
        out.append(line)
        m = re.search(pattern, line)
        if m:
            return m, out
    raise AssertionError(f"pattern {pattern!r} not seen in {out}")


def test_serving_main_worker_and_gateway(tmp_path):
    # train + save a native model for the worker to serve
    from mmlspark_tpu.core.dataset import Dataset
    from mmlspark_tpu.models.gbdt.api import LightGBMRegressor

    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 4)).astype(np.float32)
    y = (X @ np.array([1.0, -2.0, 0.5, 0.0])).astype(np.float32)
    model = LightGBMRegressor(numIterations=5, numLeaves=7,
                              minDataInLeaf=5).fit(
        Dataset({"features": X, "label": y}))
    model_file = tmp_path / "model.txt"
    model_file.write_text(model.get_native_model())
    registry = tmp_path / "registry"

    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    procs = []
    try:
        worker = subprocess.Popen(
            [sys.executable, "-m", "mmlspark_tpu.io.serving_main", "worker",
             "--model", str(model_file), "--registry", str(registry),
             "--host", "localhost", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        procs.append(worker)
        _wait_for(worker, r"worker \w+ serving on")

        gateway = subprocess.Popen(
            [sys.executable, "-m", "mmlspark_tpu.io.serving_main", "gateway",
             "--registry", str(registry), "--host", "localhost",
             "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        procs.append(gateway)
        m, _ = _wait_for(gateway, r"gateway on ([\w.]+):(\d+)")
        host, port = m.group(1), int(m.group(2))

        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/serving",
                     body=json.dumps({"features": X[0].tolist()}))
        r = conn.getresponse()
        body = json.loads(r.read())
        conn.close()
        assert r.status == 200, body
        direct = float(model.transform(
            Dataset({"features": X[:1]})).array("prediction")[0])
        assert abs(float(body["prediction"]) - direct) < 1e-5
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


class TestBenchRegression:
    """tools/bench_regression.py gates the newest BENCH_r*.json against
    the median of up to the 3 preceding rounds (>20% throughput drops) —
    exercised on synthetic fixtures (real rounds are measurements and
    must not gate the suite)."""

    def _write_round(self, d, n, line):
        # the driver wrapper shape: bench stdout lives in "tail", last
        # JSON line wins
        (d / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "tail": "noise line\n" + json.dumps(line) + "\n"}))

    def _run(self, d, *extra):
        return subprocess.run(
            [sys.executable, os.path.join(TOOLS, "bench_regression.py"),
             str(d), *extra],
            capture_output=True, text=True, timeout=60)

    def test_pass_and_regression_exit_codes(self, tmp_path):
        base = {"metric": "gbdt_trees_per_sec", "value": 10.0,
                "gbdt_predict_rows_per_sec": 1000.0,
                "broken_rows_per_sec": -1.0,       # failed secondary: skip
                "serving_p50_ms": 1.0}             # not a throughput key
        self._write_round(tmp_path, 1, base)
        ok = dict(base, value=8.5, gbdt_predict_rows_per_sec=900.0,
                  serving_p50_ms=100.0)            # 15%/10% drops: fine
        self._write_round(tmp_path, 2, ok)
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout

        bad = dict(base, gbdt_predict_rows_per_sec=500.0)   # 50% drop
        self._write_round(tmp_path, 3, bad)
        r = self._run(tmp_path)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "gbdt_predict_rows_per_sec" in r.stdout

    def test_value_gated_only_on_matching_metric(self, tmp_path):
        self._write_round(tmp_path, 1, {
            "metric": "gbdt_trees_per_sec_1M_rows_28f", "value": 30.0})
        # a toy CPU round must not gate against a TPU round's value
        self._write_round(tmp_path, 2, {
            "metric": "gbdt_trees_per_sec_50k_rows_28f",
            "value": 3.0})
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_single_round_is_a_pass(self, tmp_path):
        self._write_round(tmp_path, 1, {"metric": "m", "value": 1.0})
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_round_missing_metric_key_never_gates_value(self, tmp_path):
        # a round that lost its "metric" name (wrapper crash mid-write)
        # must not have its "value" gated against anything — and must
        # not crash the comparison
        self._write_round(tmp_path, 1, {"value": 30.0,
                                        "gbdt_predict_rows_per_sec": 100.0})
        self._write_round(tmp_path, 2, {"value": 3.0,
                                        "gbdt_predict_rows_per_sec": 95.0})
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_median_absorbs_one_hot_outlier_round(self, tmp_path):
        # the r04->r05 false flag: one anomalously FAST round must not
        # become the bar every later round is measured against
        for n, v in ((1, 100.0), (2, 104.0), (3, 160.0)):   # r3 = outlier
            self._write_round(tmp_path, n, {"metric": "m", "value": 1.0,
                                            "quantized_trees_per_sec": v})
        self._write_round(tmp_path, 4, {"metric": "m", "value": 1.0,
                                        "quantized_trees_per_sec": 98.0})
        r = self._run(tmp_path)
        # vs r3 alone: 39% drop, a false flag; vs median 104: 5.8%, fine
        assert r.returncode == 0, r.stdout + r.stderr
        assert "median(r01,r02,r03)" in r.stdout

        # a drop below the MEDIAN still gates — the window absorbs
        # jitter, not sustained regressions
        self._write_round(tmp_path, 5, {"metric": "m", "value": 1.0,
                                        "quantized_trees_per_sec": 60.0})
        r = self._run(tmp_path)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "quantized_trees_per_sec" in r.stdout

    def test_even_window_takes_lower_median(self, tmp_path):
        # two baseline rounds at 100 and 130: the LOWER middle (100) is
        # the bar, so 85 is a 15% drop, not a 34.6% flag
        self._write_round(tmp_path, 1, {"x_per_sec": 100.0})
        self._write_round(tmp_path, 2, {"x_per_sec": 130.0})
        self._write_round(tmp_path, 3, {"x_per_sec": 85.0})
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_window_flag_narrows_baseline(self, tmp_path):
        for n, v in ((1, 500.0), (2, 500.0), (3, 100.0)):
            self._write_round(tmp_path, n, {"x_per_sec": v})
        self._write_round(tmp_path, 4, {"x_per_sec": 95.0})
        # --window 1 = the old previous-round-only behaviour
        assert self._run(tmp_path, "--window", "1").returncode == 0
        # the full window medians to 500 -> 81% drop
        assert self._run(tmp_path).returncode == 1

    def test_unparseable_baseline_round_shrinks_window(self, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text("no json here\n")
        self._write_round(tmp_path, 2, {"x_per_sec": 100.0})
        self._write_round(tmp_path, 3, {"x_per_sec": 97.0})
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "skipping unparseable baseline" in r.stderr

    def test_mixed_metric_window_never_gates_value(self, tmp_path):
        # a window mixing a TPU round and a CPU fallback must drop the
        # headline "value" from the baseline entirely
        self._write_round(tmp_path, 1, {"metric": "tpu_m", "value": 30.0})
        self._write_round(tmp_path, 2, {"metric": "cpu_m", "value": 3.0})
        self._write_round(tmp_path, 3, {"metric": "tpu_m", "value": 4.0})
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_compare_tolerates_missing_keys(self):
        sys.path.insert(0, TOOLS)
        try:
            import bench_regression as br
        finally:
            sys.path.remove(TOOLS)
        # public helper, arbitrary dicts: a key present in one round
        # only is skipped, not a KeyError
        assert br.compare({"x_per_sec": 10.0, "metric": "m", "value": 1.0},
                          {"metric": "m", "value": 1.0},
                          threshold=0.2) == []


class TestRooflineTrend:
    """tools/roofline_report.py in multi-round mode renders the measured
    ``*_roofline_pct`` keys as a trend table across BENCH_r*.json driver
    wrappers (report-only — bench_regression's gate ignores these keys)."""

    def _write_round(self, d, n, line):
        (d / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "tail": "noise line\n" + json.dumps(line) + "\n"}))

    def _run(self, *paths):
        return subprocess.run(
            [sys.executable, os.path.join(TOOLS, "roofline_report.py"),
             *[str(p) for p in paths]],
            capture_output=True, text=True, timeout=60)

    def test_trend_across_rounds(self, tmp_path):
        self._write_round(tmp_path, 1, {
            "metric": "m", "value": 1.0,
            "gbdt_predict_roofline_pct": 40.2,
            "serving_score_roofline_pct": 12.5})
        # CPU leg: peaks unknown, keys absent by design -> "-" cells
        self._write_round(tmp_path, 2, {"metric": "m_CPU", "value": 0.1})
        self._write_round(tmp_path, 3, {
            "metric": "m", "value": 1.1,
            "gbdt_predict_roofline_pct": 46.5})
        r = self._run(tmp_path / "BENCH_r01.json",
                      tmp_path / "BENCH_r02.json",
                      tmp_path / "BENCH_r03.json")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "roofline %-of-peak trend" in r.stdout
        row = next(ln for ln in r.stdout.splitlines()
                   if ln.startswith("gbdt_predict_roofline_pct"))
        assert "40.2%" in row and "46.5%" in row and "-" in row
        assert "+6.30pp" in row
        # serving key present in one round only: no trend arithmetic
        row = next(ln for ln in r.stdout.splitlines()
                   if ln.startswith("serving_score_roofline_pct"))
        assert row.rstrip().endswith("-")

    def test_rounds_without_keys_render_honest_message(self, tmp_path):
        self._write_round(tmp_path, 1, {"metric": "cpu", "value": 1.0})
        self._write_round(tmp_path, 2, {"metric": "cpu", "value": 1.0})
        r = self._run(tmp_path / "BENCH_r01.json",
                      tmp_path / "BENCH_r02.json")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "no *_roofline_pct keys" in r.stdout

    def test_single_wrapper_falls_back_to_one_column(self, tmp_path):
        self._write_round(tmp_path, 1, {
            "metric": "m", "value": 1.0,
            "gbdt_predict_roofline_pct": 33.0})
        r = self._run(tmp_path / "BENCH_r01.json")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "33%" in r.stdout


def test_docker_tree_well_formed():
    for rel in ("docker/minimal/Dockerfile", "docker/serving/Dockerfile"):
        text = open(os.path.join(TOOLS, rel)).read()
        assert text.startswith("# ")
        assert "FROM " in text and "pip install" in text
    compose = open(os.path.join(TOOLS, "docker/demo/docker-compose.yml")).read()
    yaml = pytest.importorskip("yaml")
    d = yaml.safe_load(compose)
    assert set(d["services"]) == {"gateway", "worker-1", "worker-2"}
    assert "registry" in d["volumes"]


def test_helm_chart_well_formed():
    yaml = pytest.importorskip("yaml")
    chart = yaml.safe_load(open(os.path.join(
        TOOLS, "helm/serving/Chart.yaml")))
    assert chart["name"] == "mmlspark-tpu-serving"
    values = yaml.safe_load(open(os.path.join(
        TOOLS, "helm/serving/values.yaml")))
    assert values["workers"]["replicas"] >= 1
    tdir = os.path.join(TOOLS, "helm/serving/templates")
    templates = sorted(os.listdir(tdir))
    assert {"worker-deployment.yaml", "gateway-deployment.yaml",
            "gateway-service.yaml", "registry-pvc.yaml"} <= set(templates)
    for t in templates:
        text = open(os.path.join(tdir, t)).read()
        # balanced go-template braces
        assert text.count("{{") == text.count("}}"), t
