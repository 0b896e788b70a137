"""Resilience policy: breakers, budgets, backoff, deadlines, drain, chaos.

Covers robustness/policy.py and its wiring through the serving stack:

* circuit-breaker state machine (closed/open/half-open, hard + soft +
  error-rate trips) and the retry-budget token bucket;
* full-jitter backoff honoring Retry-After, and advanced_handling
  routing its sleeps through the policy funnel with counted retries;
* worker admission control: bounded queue -> 429 + Retry-After derived
  from observed batch latency, and the queue-wait histogram;
* deadline propagation edge -> gateway -> worker (attenuated per hop,
  one trace_id) and expired-deadline drops at admission and in-batch;
* the acceptance scenarios: a SIGTERM'd worker drains with ZERO
  client-visible errors, and a 3-process chaos run (worker kill + 20%
  injected 503s + latency spikes) sustains >= 99% success with no
  duplicate replies and breakers observed opening then re-closing.
"""

import http.client
import json
import os
import queue
import random
import re
import signal
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from mmlspark_tpu.io.distributed_serving import (DistributedServing,
                                                 GatewayServer,
                                                 ServiceRegistry)
from mmlspark_tpu.io.http import HTTPRequestData, advanced_handling
from mmlspark_tpu.io.serving import ServedRequest, ServingQuery, ServingServer
from mmlspark_tpu.observability import flight, metrics
from mmlspark_tpu.observability.federation import parse_prometheus_text
from mmlspark_tpu.robustness import failpoints, policy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ID = "a" * 32
TRACEPARENT = f"00-{TRACE_ID}-{'b' * 16}-01"


@pytest.fixture(autouse=True)
def _clean():
    prev = metrics.set_enabled(True)
    metrics.reset()
    flight.clear()
    failpoints.clear()
    yield
    failpoints.clear()
    metrics.set_enabled(prev)
    metrics.reset()
    flight.clear()


# ---------------------------------------------------------------------------
# Policy units
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def _breaker(self, **kw):
        kw.setdefault("consecutive_failures", 3)
        kw.setdefault("min_volume", 100)    # rate trip off unless asked
        kw.setdefault("open_seconds", 10.0)
        clock = [0.0]
        b = policy.CircuitBreaker("w", policy.BreakerConfig(**kw),
                                  clock=lambda: clock[0])
        return b, clock

    def test_consecutive_failures_open(self):
        b, _ = self._breaker()
        b.record_failure()
        b.record_failure()
        assert b.state == policy.CLOSED and b.allow()
        b.record_failure()
        assert b.state == policy.OPEN and not b.allow()

    def test_success_resets_consecutive(self):
        b, _ = self._breaker()
        b.record_failure()
        b.record_failure()
        b.record_success()
        b.record_failure()
        b.record_failure()
        assert b.state == policy.CLOSED

    def test_hard_failure_opens_immediately(self):
        b, _ = self._breaker()
        b.record_failure(hard=True)
        assert b.state == policy.OPEN

    def test_error_rate_trip(self):
        b, _ = self._breaker(consecutive_failures=1000, min_volume=10,
                             window=10, error_rate=0.5)
        for _ in range(5):
            b.record_success()
        for _ in range(5):
            b.record_failure()
        assert b.state == policy.OPEN

    def test_half_open_recovery_and_reopen(self):
        b, clock = self._breaker()
        b.record_failure(hard=True)
        assert not b.probe_due() and not b.begin_probe()
        clock[0] = 11.0
        assert b.probe_due() and b.begin_probe()
        assert b.state == policy.HALF_OPEN and not b.allow()
        b.probe_failure()                       # probe failed
        assert b.state == policy.OPEN
        clock[0] = 23.0
        assert b.begin_probe()
        b.probe_success()                       # probe succeeded
        assert b.state == policy.CLOSED and b.allow()

    def test_stale_inflight_results_cannot_flip_half_open(self):
        """A request that was in flight when the breaker tripped must
        not drive recovery: only the health loop's probe verdicts may
        move a HALF_OPEN breaker."""
        b, clock = self._breaker()
        b.record_failure(hard=True)
        clock[0] = 11.0
        b.begin_probe()
        b.record_failure(hard=True)             # stale live-traffic result
        assert b.state == policy.HALF_OPEN      # cooldown NOT restarted
        b.record_success()                      # stale success either
        assert b.state == policy.HALF_OPEN
        b.probe_success()
        assert b.state == policy.CLOSED

    def test_transitions_observable(self):
        b, clock = self._breaker()
        b.record_failure(hard=True)
        clock[0] = 11.0
        b.begin_probe()
        b.probe_success()
        assert metrics.counter("breaker_transitions_total", worker="w",
                               to="open").value == 1.0
        assert metrics.counter("breaker_transitions_total", worker="w",
                               to="closed").value == 1.0
        assert metrics.gauge("breaker_state", worker="w").value == 0.0
        seq = [(e["frm"], e["to"]) for e in flight.events()
               if e["kind"] == "breaker_transition"]
        assert seq == [("closed", "open"), ("open", "half_open"),
                       ("half_open", "closed")]

    def test_board_allows_unknown_keys(self):
        board = policy.BreakerBoard()
        assert board.allow("never-seen")
        board.breaker("w1").record_failure(hard=True)
        assert not board.allow("w1") and board.allow("w2")


class TestRetryBudget:
    def test_exhaustion_and_deposits(self):
        b = policy.RetryBudget(ratio=0.5, min_tokens=2, cap=10, api="t")
        assert b.try_spend() and b.try_spend()
        assert not b.try_spend()            # exhausted
        for _ in range(4):
            b.deposit()                     # 4 * 0.5 = 2 tokens back
        assert b.try_spend() and b.try_spend() and not b.try_spend()
        assert metrics.counter("retry_budget_spent_total",
                               api="t").value == 4.0
        assert metrics.counter("retry_budget_exhausted_total",
                               api="t").value == 2.0
        assert any(e["kind"] == "retry_budget_exhausted"
                   for e in flight.events())

    def test_cap_bounds_accrual(self):
        b = policy.RetryBudget(ratio=1.0, min_tokens=1, cap=3)
        for _ in range(50):
            b.deposit()
        assert b.tokens == 3.0


class TestBackoff:
    def test_full_jitter_within_schedule_step(self):
        rng = random.Random(0)
        for attempt, upper in ((0, 100), (1, 500), (2, 1000), (5, 1000)):
            for _ in range(50):
                d = policy.backoff_delay(attempt,
                                         schedule_ms=(100, 500, 1000),
                                         rng=rng)
                assert 0.0 <= d <= upper / 1000.0

    def test_exponential_default_caps(self):
        rng = random.Random(1)
        assert all(policy.backoff_delay(20, cap_ms=2000, rng=rng) <= 2.0
                   for _ in range(20))

    def test_retry_after_overrides_and_caps(self):
        assert policy.backoff_delay(0, retry_after="2.5") == 2.5
        assert policy.backoff_delay(0, retry_after="9999") == 30.0
        # HTTP-date (non-numeric) falls back to the jittered schedule
        d = policy.backoff_delay(0, schedule_ms=(100,),
                                 retry_after="Wed, 21 Oct 2015 07:28:00 GMT",
                                 rng=random.Random(2))
        assert 0.0 <= d <= 0.1

    def test_backoff_sleeps_the_delay(self):
        slept = []
        d = policy.backoff(1, schedule_ms=(50, 80),
                           rng=random.Random(3), sleep=slept.append)
        assert slept == [d] and 0.0 < d <= 0.08


class TestDeadline:
    def test_parse_and_attenuate(self):
        clock = [100.0]
        d = policy.Deadline.from_headers({"X-Deadline-Ms": "500"},
                                         clock=lambda: clock[0])
        assert d is not None and not d.expired
        assert d.remaining_ms() == pytest.approx(500.0)
        assert d.header_value(margin_ms=20) == "480"
        clock[0] = 100.3
        assert d.header_value(margin_ms=20) == "180"
        clock[0] = 101.0
        assert d.expired and d.remaining_seconds() == 0.0
        assert d.header_value(margin_ms=20) == "0"

    def test_lowercased_and_missing_headers(self):
        assert policy.Deadline.from_headers(
            {"x-deadline-ms": "100"}) is not None
        assert policy.Deadline.from_headers({}) is None
        assert policy.Deadline.from_headers(None) is None
        assert policy.Deadline.from_headers(
            {"X-Deadline-Ms": "soon"}) is None   # malformed -> no deadline


# ---------------------------------------------------------------------------
# advanced_handling through the policy funnel
# ---------------------------------------------------------------------------


class _Flaky:
    """Local endpoint answering N retryable statuses, then 200."""

    def __init__(self, plan):
        self.plan = list(plan)
        self.seen = 0
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                outer.seen += 1
                if outer.plan:
                    status, headers = outer.plan.pop(0)
                else:
                    status, headers = 200, {}
                body = b"ok" if status == 200 else b"busy"
                self.send_response(status)
                for k, v in headers.items():
                    self.send_header(k, v)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        self.httpd = ThreadingHTTPServer(("localhost", 0), Handler)
        self.url = f"http://localhost:{self.httpd.server_address[1]}/"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class TestAdvancedHandling:
    def test_jittered_schedule_and_retry_counter(self, monkeypatch):
        calls = []
        real = policy.backoff

        def spy(attempt, **kw):
            kw["sleep"] = lambda s: None      # no real waiting in tests
            d = real(attempt, **kw)
            calls.append((attempt, kw.get("retry_after"), d))
            return d

        monkeypatch.setattr(policy, "backoff", spy)
        srv = _Flaky([(503, {"Retry-After": "0.02"}), (503, {})])
        try:
            resp = advanced_handling(HTTPRequestData(url=srv.url),
                                     backoffs=(40, 80, 120))
        finally:
            srv.close()
        assert resp.status_code == 200 and srv.seen == 3
        assert len(calls) == 2
        # first step honored the server's Retry-After exactly
        assert calls[0][1] == "0.02" and calls[0][2] == 0.02
        # second step: full jitter within its schedule entry
        assert calls[1][1] is None and 0.0 <= calls[1][2] <= 0.08
        assert metrics.counter("http_retries_total",
                               reason="503").value == 2.0

    def test_connection_failures_counted_separately(self, monkeypatch):
        monkeypatch.setattr(policy, "backoff",
                            lambda attempt, **kw: 0.0)
        resp = advanced_handling(
            HTTPRequestData(url="http://localhost:1/refused"),
            backoffs=(1, 1))
        assert resp.status_code == 0
        assert metrics.counter("http_retries_total",
                               reason="connection").value == 2.0


# ---------------------------------------------------------------------------
# Worker admission control + queue wait
# ---------------------------------------------------------------------------


def _request(host, port, path, body=None, headers=None, timeout=30,
             method=None):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request(method or ("POST" if body is not None else "GET"),
                 path, body=body, headers=headers or {})
    r = conn.getresponse()
    payload = r.read()
    hdrs = dict(r.getheaders())
    conn.close()
    return r.status, payload, hdrs


def _echo_query(**kw):
    server = ServingServer("localhost", 0, "res", **kw)
    q = ServingQuery(server, lambda ds: ds.with_column("reply", [
        {"entity": {"i": v["i"]}, "statusCode": 200}
        for v in ds["value"]]), max_batch=8, max_latency=0.005)
    return q.start()


class TestAdmissionControl:
    def test_bounded_queue_sheds_with_retry_after(self):
        # no batch consumer: requests park, the queue fills, and the
        # admission bound sheds the overflow with a drain-time hint
        server = ServingServer("localhost", 0, "shed", request_timeout=1.0,
                               max_queue_depth=1)
        server.start()
        try:
            done = queue.Queue()
            threading.Thread(
                target=lambda: done.put(_request(
                    server.host, server.port, "/shed", b"{}")),
                daemon=True).start()
            deadline = time.monotonic() + 5
            while server._queue.qsize() < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            status, body, hdrs = _request(server.host, server.port,
                                          "/shed", b"{}")
            assert status == 429, body
            assert int(hdrs["Retry-After"]) >= 1
            assert metrics.counter("serving_shed_total", api="shed",
                                   reason="queue_full").value == 1.0
            assert any(e["kind"] == "shed" for e in flight.events())
            # a shed counts ONCE, as a 429 — not also as a phantom 504
            # (exact-count parity with the async engine's accounting).
            # Polled: the client sees the response bytes a beat before
            # the handler thread's finally-block accounting runs
            ctr = metrics.counter("serving_responses_total", api="shed",
                                  code="429")
            deadline = time.monotonic() + 5
            while ctr.value < 1.0 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert ctr.value == 1.0
            assert done.get(timeout=10)[0] == 504   # the parked request
        finally:
            server.stop()

    def test_queue_wait_histogram_observed(self):
        q = _echo_query()
        try:
            for i in range(3):
                status, body, _ = _request(q.server.host, q.server.port,
                                           "/res", json.dumps({"i": i}))
                assert status == 200
        finally:
            q.stop()
        snap = metrics.get_registry().snapshot()
        series = snap["serving_queue_wait_seconds"]["series"]
        assert series and series[0]["count"] >= 3
        # the shed hint machinery saw the same signal
        assert q.server._wait_ewma.value is not None

    def test_chunked_transfer_rejected_loudly(self):
        # the HTTP/1.1 keep-alive handlers don't decode chunked framing:
        # they must answer 411 and close, never desync the persistent
        # connection on an unread payload
        q = _echo_query()
        try:
            status, body, _ = _request(
                q.server.host, q.server.port, "/res", b"5\r\nhello\r\n0\r\n\r\n",
                headers={"Transfer-Encoding": "chunked"})
            assert status == 411 and b"Content-Length" in body
            # the server is fine afterwards
            status, _, _ = _request(q.server.host, q.server.port, "/res",
                                    json.dumps({"i": 7}))
            assert status == 200
        finally:
            q.stop()

    def test_drain_refuses_new_accepts_inflight(self):
        q = _echo_query()
        host, port = q.server.host, q.server.port
        status, _, _ = _request(host, port, "/res",
                                json.dumps({"i": 1}))
        assert status == 200
        q.server.begin_drain()
        status, body, hdrs = _request(host, port, "/res",
                                      json.dumps({"i": 2}))
        assert status == 503 and b"draining" in body
        assert "Retry-After" in hdrs
        q.stop()


# ---------------------------------------------------------------------------
# Deadlines end-to-end (edge -> gateway -> worker, one trace_id)
# ---------------------------------------------------------------------------


def _deadline_echo_transform(ds):
    replies = []
    for h, v in zip(ds["headers"], ds["value"]):
        replies.append({"entity": {"deadline": h.get("x-deadline-ms"),
                                   "i": (v or {}).get("i")},
                        "statusCode": 200})
    return ds.with_column("reply", replies)


class TestDeadlinePropagation:
    def test_attenuated_across_gateway_with_one_trace_id(self):
        d = DistributedServing(_deadline_echo_transform,
                               num_workers=2).start()
        try:
            status, body, hdrs = _request(
                d.gateway.host, d.gateway.port, "/serving",
                json.dumps({"i": 4}),
                headers={policy.DEADLINE_HEADER: "5000",
                         "traceparent": TRACEPARENT})
            assert status == 200
            reply = json.loads(body)
            assert reply["i"] == 4
            # the worker saw the budget minus the gateway hop's margin
            seen = float(reply["deadline"])
            assert 3000.0 < seen < 5000.0
            # one trace identity across edge -> gateway -> worker
            assert hdrs["X-Request-Id"] == TRACE_ID
        finally:
            d.stop()

    def test_expired_deadline_fails_fast_at_gateway(self):
        d = DistributedServing(_deadline_echo_transform,
                               num_workers=1).start()
        try:
            t0 = time.monotonic()
            status, body, hdrs = _request(
                d.gateway.host, d.gateway.port, "/serving",
                json.dumps({"i": 1}),
                headers={policy.DEADLINE_HEADER: "0"})
            dt = time.monotonic() - t0
            assert status == 504 and b"deadline" in body
            assert "Retry-After" in hdrs
            assert dt < 1.0                     # never waited on a worker
            assert metrics.counter("gateway_deadline_expired_total",
                                   api="serving").value == 1.0
        finally:
            d.stop()

    def test_expired_deadline_rejected_at_worker_admission(self):
        q = _echo_query()
        try:
            status, body, _ = _request(q.server.host, q.server.port,
                                       "/res", json.dumps({"i": 1}),
                                       headers={policy.DEADLINE_HEADER:
                                                "0"})
            assert status == 504
            assert metrics.counter("serving_deadline_dropped_total",
                                   api="res", stage="admission").value \
                == 1.0
        finally:
            q.stop()

    def test_batch_loop_drops_expired_cobatched(self):
        server = ServingServer("localhost", 0, "drop")
        q = ServingQuery(server, _deadline_echo_transform)
        expired = ServedRequest(id="old", method="POST", path="/drop",
                                headers={}, body=b"{}",
                                deadline=policy.Deadline.from_ms(-5))
        fresh = ServedRequest(id="new", method="POST", path="/drop",
                              headers={}, body=b"{}",
                              deadline=policy.Deadline.from_ms(60_000))
        with server._lock:
            server._inflight["old"] = expired
            server._inflight["new"] = fresh
        live = q._drop_expired([expired, fresh], "drop")
        assert live == [fresh]
        assert expired.done.is_set()
        assert expired.response["statusCode"] == 504
        assert not fresh.done.is_set()
        assert metrics.counter("serving_deadline_dropped_total",
                               api="drop", stage="batch").value == 1.0
        assert any(e["kind"] == "deadline_dropped"
                   and e["request_id"] == "old" for e in flight.events())


class TestGatewayRetryAfter:
    def test_shed_429_fails_over_without_breaker_strike(self):
        """A worker shedding with 429 is overloaded, not broken: the
        gateway retries the request elsewhere but must NOT strike the
        worker's breaker — opening it would remove capacity exactly
        when the cluster is short of it."""
        failpoints.configure("gateway.route:error_429@1")
        d = DistributedServing(_deadline_echo_transform,
                               num_workers=2).start()
        try:
            status, body, _ = _request(d.gateway.host, d.gateway.port,
                                       "/serving", json.dumps({"i": 3}))
            assert status == 200 and json.loads(body)["i"] == 3
            assert metrics.counter("gateway_retries_total", api="serving",
                                   reason="status_429").value == 1.0
            assert all(b.state == policy.CLOSED
                       for _, b in d.gateway.breakers.items())
        finally:
            d.stop()

    def test_no_live_workers_503_carries_retry_after(self):
        gw = GatewayServer(ServiceRegistry(), "localhost", 0,
                           "serving").start()
        try:
            status, _, hdrs = _request(gw.host, gw.port, "/serving",
                                       b"{}")
            assert status == 503
            assert int(hdrs["Retry-After"]) >= 1
        finally:
            gw.stop()


# ---------------------------------------------------------------------------
# Process-level acceptance: graceful drain + chaos
# ---------------------------------------------------------------------------


def _wait_for(proc, pattern, timeout=90):
    # ONE reader thread per process for its whole life: a second reader
    # on the same pipe would race the first for lines and lose them
    q = getattr(proc, "_outq", None)
    if q is None:
        q = proc._outq = queue.Queue()

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


def _spawn_worker(registry, env, port=0, engine=None):
    cmd = [sys.executable, "-m", "tests._chaos_worker",
           "--registry", str(registry), "--port", str(port)]
    if engine:
        cmd += ["--engine", engine]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    m, _ = _wait_for(proc, r"worker \w+ serving on ([\w.]+):(\d+)")
    return proc, int(m.group(2))


def _spawn_gateway(registry, env):
    proc = subprocess.Popen(
        [sys.executable, "-m", "mmlspark_tpu.io.serving_main",
         "gateway", "--registry", str(registry),
         "--host", "localhost", "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    m, _ = _wait_for(proc, r"gateway on ([\w.]+):(\d+)")
    return proc, m.group(1), int(m.group(2))


def _gateway_env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    env.pop(failpoints.FAILPOINTS_ENV, None)
    env.pop(failpoints.SEED_ENV, None)
    env.update(extra or {})
    return env


def _warm_workers(host, port, n_workers, timeout=60):
    """First request per worker pays its lazy imports (seconds under
    suite load) — warm every worker through the gateway so the measured
    traffic sees steady-state latency."""
    seen = set()
    deadline = time.monotonic() + timeout
    k = 0
    while len(seen) < n_workers and time.monotonic() < deadline:
        status, body, _ = _request(host, port, "/serving",
                                   json.dumps({"i": -1 - k}))
        k += 1
        if status == 200:
            seen.add(json.loads(body).get("pid"))
    assert len(seen) >= n_workers, f"warmed only {seen}"


class TestGracefulDrain:
    @pytest.mark.chaos
    # the async variant is slow-marked per the tier-1 wall budget (>10 s
    # of subprocess spawns + fixed drain waits); ci lanes still run it,
    # and the in-process drain contract rides tier-1 in test_aserve
    @pytest.mark.parametrize("engine", [
        "threaded", pytest.param("async", marks=pytest.mark.slow)])
    def test_sigterm_drain_zero_client_visible_errors(self, tmp_path,
                                                      engine):
        """Continuous traffic through the gateway while one of two
        workers is SIGTERM'd: every request answers 200 with its own
        echo, the drained worker exits cleanly, and its registry entry
        is gone. Both serving engines keep this contract — the drain
        plane is engine-transparent."""
        registry = tmp_path / "registry"
        env = _gateway_env({
            "MMLSPARK_TPU_GATEWAY_HEALTH_INTERVAL_SECONDS": "0.3",
            "MMLSPARK_TPU_DRAIN_SETTLE_SECONDS": "0.4",
        })
        wa, porta = _spawn_worker(registry, env, engine=engine)
        wb, portb = _spawn_worker(registry, env, engine=engine)
        gw, host, port = _spawn_gateway(registry, env)
        _warm_workers(host, port, 2)
        results, stop = [], threading.Event()

        def client():
            k = 0
            while not stop.is_set():
                try:
                    status, body, _ = _request(host, port, "/serving",
                                               json.dumps({"i": k}))
                    results.append((k, status, body))
                except Exception as e:  # noqa: BLE001 — a failure IS the signal
                    results.append((k, -1, repr(e)))
                k += 1

        t = threading.Thread(target=client, daemon=True)
        try:
            t.start()
            time.sleep(0.8)
            wa.send_signal(signal.SIGTERM)
            _wait_for(wa, r"drained")
            assert wa.wait(timeout=30) == 0
            time.sleep(0.8)                  # traffic continues on B
            # the drained worker deregistered; only B remains
            remaining = [f for f in os.listdir(registry)
                         if f.endswith(".json")]
            assert len(remaining) == 1
        finally:
            stop.set()
            t.join(timeout=30)
            for p in (wa, wb, gw):
                p.terminate()
            for p in (wb, gw):
                p.wait(timeout=30)

        assert len(results) > 20
        bad = [r for r in results if r[1] != 200]
        assert not bad, f"client-visible errors during drain: {bad[:5]}"
        for k, _, body in results:
            assert json.loads(body)["i"] == k


_FIT_DRIVER = """
import sys
import numpy as np
from mmlspark_tpu.core.dataset import Dataset
from mmlspark_tpu.models.gbdt.api import LightGBMClassifier

out, ckpt = sys.argv[1], sys.argv[2]
rng = np.random.default_rng(5)
X = rng.normal(size=(240, 5)).astype(np.float32)
y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
ds = Dataset({"features": X, "label": y})
model = LightGBMClassifier(numIterations=12, numLeaves=7, minDataInLeaf=5,
                           checkpointDir=ckpt,
                           checkpointInterval=3).fit(ds)
with open(out, "w") as f:
    f.write(model.booster.model_string())
"""


class TestPreemptionResume:
    @pytest.mark.chaos
    def test_killed_mid_fit_resumes_bit_identical(self, tmp_path):
        """The MLPerf-pod contract: a fit preempted mid-train (os._exit
        at round 8, no cleanup — exactly a SIGKILL) resumes from its
        last checkpoint to the SAME trees, bit for bit, as a run that
        was never interrupted. Checkpoints carry the accumulated score
        matrix, so the resumed rounds see identical float state."""
        env = _gateway_env()

        def fit(out, ckpt, extra=None):
            e = dict(env)
            e.update(extra or {})
            return subprocess.run(
                [sys.executable, "-c", _FIT_DRIVER, str(out), str(ckpt)],
                env=e, capture_output=True, text=True, timeout=600)

        control = fit(tmp_path / "control.txt", tmp_path / "ck_control")
        assert control.returncode == 0, control.stderr[-2000:]

        # preempted run: hard os._exit on the 8th boosting round — after
        # the round-6 checkpoint, before the fit could finish
        killed = fit(tmp_path / "never.txt", tmp_path / "ck",
                     {failpoints.FAILPOINTS_ENV: "gbdt.round:exit@8"})
        assert killed.returncode == 17, (killed.returncode, killed.stderr)
        assert not (tmp_path / "never.txt").exists()

        resumed = fit(tmp_path / "resumed.txt", tmp_path / "ck")
        assert resumed.returncode == 0, resumed.stderr[-2000:]

        a = (tmp_path / "control.txt").read_text()
        b = (tmp_path / "resumed.txt").read_text()
        assert a == b, "resumed trees differ from the uninterrupted run"

    @pytest.mark.chaos
    def test_sharded_kill_resumes_on_smaller_mesh(self, tmp_path):
        """The sharded round loop wears the whole robustness plane: a fit
        hard-killed mid-round on an 8-device mesh resumes — on a 2-DEVICE
        mesh — to trees bit-identical with an uninterrupted 8-device run.
        Works because (a) checkpoints carry the exact accumulated score
        matrices (gathered to host, so the payload is topology-free) and
        (b) MMLSPARK_TPU_HIST_BLOCKS=8 pins the canonical histogram
        reduction geometry, making the remaining rounds independent of the
        device count (tests/test_placement.py proves the general
        identity)."""
        det = {"MMLSPARK_TPU_HIST_BLOCKS": "8", "JAX_PLATFORMS": "cpu"}

        def fit(out, ckpt, devices, extra=None):
            e = _gateway_env(det)
            e["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={devices}"
            e.update(extra or {})
            return subprocess.run(
                [sys.executable, "-c", _FIT_DRIVER, str(out), str(ckpt)],
                env=e, capture_output=True, text=True, timeout=600)

        control = fit(tmp_path / "control.txt", tmp_path / "ck_c", 8)
        assert control.returncode == 0, control.stderr[-2000:]

        killed = fit(tmp_path / "never.txt", tmp_path / "ck", 8,
                     {failpoints.FAILPOINTS_ENV: "gbdt.round:exit@8"})
        assert killed.returncode == 17, (killed.returncode, killed.stderr)
        assert not (tmp_path / "never.txt").exists()

        resumed = fit(tmp_path / "resumed.txt", tmp_path / "ck", 2)
        assert resumed.returncode == 0, resumed.stderr[-2000:]
        assert (tmp_path / "control.txt").read_text() == \
            (tmp_path / "resumed.txt").read_text(), \
            "2-device resume diverged from the uninterrupted 8-device run"


class TestShardedRobustnessPlane:
    """gbdt.round failpoints + the round-loop heartbeat fire under
    shard_map exactly as they do single-device (the host loop hosting them
    is topology-agnostic; these pin that it stays so)."""

    @staticmethod
    def _fit(**kw):
        import numpy as np

        from mmlspark_tpu.models.gbdt.booster import train_booster
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        rng = np.random.default_rng(5)
        X = rng.normal(size=(240, 5)).astype(np.float32)
        y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
        cfg = GrowConfig(num_leaves=7, min_data_in_leaf=5)
        # iteration_callback pins the HOST round loop (the fused
        # single-dispatch paths have no per-round failpoint evaluation)
        return train_booster(X, y, objective="binary", num_iterations=4,
                             cfg=cfg, max_bin=63, bin_sample_count=240,
                             iteration_callback=lambda it, m: None, **kw)

    @pytest.mark.chaos
    def test_round_failpoint_fires_in_sharded_fit(self):
        from mmlspark_tpu.observability import metrics

        failpoints.configure("gbdt.round:error@2", seed=3)
        try:
            with pytest.raises(failpoints.InjectedFault):
                self._fit()
        finally:
            failpoints.clear()
        assert metrics.counter("failpoints_fired_total", site="gbdt.round",
                               kind="error").value >= 1.0

    def test_round_heartbeat_lives_and_closes(self):
        from mmlspark_tpu.observability import watchdog

        beats = []
        orig = watchdog.register

        def spying(site, **kw):
            hb = orig(site, **kw)
            if site == "gbdt_round_loop":
                beats.append(hb)
            return hb

        watchdog.register = spying
        try:
            self._fit()
        finally:
            watchdog.register = orig
        assert beats, "sharded host round loop never registered its " \
                      "heartbeat"


class TestChaosAcceptance:
    @pytest.mark.chaos
    def test_three_process_chaos_run(self, tmp_path):
        """2 workers + gateway under worker SIGKILL + 20% injected
        worker-hop 503s + worker latency spikes: >= 99% success, every
        reply matches its own request (no duplicates / cross-wiring),
        and the killed worker's breaker opens, half-opens, and re-closes
        after the worker returns — all visible in the gateway's flight
        ring."""
        registry = tmp_path / "registry"
        worker_env = _gateway_env({
            failpoints.FAILPOINTS_ENV: "serving.handle:delay:30ms:0.08",
            failpoints.SEED_ENV: "11",
        })
        gateway_env = _gateway_env({
            failpoints.FAILPOINTS_ENV: "gateway.route:error_503:0.2",
            failpoints.SEED_ENV: "7",
            "MMLSPARK_TPU_RETRY_BUDGET_RATIO": "0.5",
            "MMLSPARK_TPU_RETRY_BUDGET_MIN": "20",
            "MMLSPARK_TPU_GATEWAY_HEALTH_INTERVAL_SECONDS": "0.25",
            "MMLSPARK_TPU_BREAKER_OPEN_SECONDS": "0.5",
        })
        wa, porta = _spawn_worker(registry, worker_env)
        wb, portb = _spawn_worker(registry, worker_env)
        gw, host, port = _spawn_gateway(registry, gateway_env)
        _warm_workers(host, port, 2)
        addr_a = f"localhost:{porta}"
        results = []

        def run_traffic(n, start):
            for k in range(start, start + n):
                try:
                    status, body, _ = _request(host, port, "/serving",
                                               json.dumps({"i": k}))
                    results.append((k, status, body))
                except Exception as e:  # noqa: BLE001
                    results.append((k, -1, repr(e)))

        try:
            run_traffic(120, 0)                      # phase 1: chaos only
            wa.kill()                                # phase 2: worker death
            wa.wait(timeout=30)
            run_traffic(60, 120)
            # phase 3: the worker returns on the SAME port; the breaker
            # must half-open via the health loop and close again
            wa2, _ = _spawn_worker(registry, worker_env, port=porta)
            deadline = time.monotonic() + 30
            closed = False
            while time.monotonic() < deadline:
                _, body, _ = _request(host, port, "/metrics")
                fams = parse_prometheus_text(body.decode())
                rows = dict((lb.get("worker"), v) for lb, v in
                            fams.get("breaker_state", ("gauge", []))[1])
                if rows.get(addr_a) == 0.0:
                    closed = True
                    break
                time.sleep(0.2)
            assert closed, "breaker for the restarted worker never closed"
            run_traffic(80, 180)

            # ---- success rate + reply integrity --------------------------
            assert len(results) == 260
            ok = [r for r in results if r[1] == 200]
            assert len(ok) / len(results) >= 0.99, [
                r for r in results if r[1] != 200][:10]
            for k, _, body in ok:
                assert json.loads(body)["i"] == k    # no cross-wiring
            assert len({k for k, _, _ in ok}) == len(ok)  # no duplicates

            # ---- breaker lifecycle + faults in the flight ring -----------
            _, body, _ = _request(host, port, "/debug/flight")
            events = json.loads(body)["events"]
            seq = [e["to"] for e in events
                   if e["kind"] == "breaker_transition"
                   and e["breaker"] == addr_a]
            assert "open" in seq and "half_open" in seq \
                and "closed" in seq, seq
            assert seq.index("open") < seq.index("closed")
            assert any(e["kind"] == "failpoint"
                       and e["site"] == "gateway.route" for e in events)

            # ---- injected chaos visible in the gateway metrics -----------
            _, body, _ = _request(host, port, "/metrics")
            fams = parse_prometheus_text(body.decode())
            injected = fams.get("failpoints_fired_total", ("counter", []))[1]
            assert sum(v for _, v in injected) >= 20   # ~20% of 260+

            # the surviving worker never saw a duplicate/unknown reply
            _, body, _ = _request("localhost", portb, "/metrics")
            assert b"serving_reply_unknown_total" not in body
        finally:
            procs = [p for p in (wa, wb, gw) if p.poll() is None]
            if 'wa2' in locals() and wa2.poll() is None:
                procs.append(wa2)
            for p in procs:
                p.terminate()
            for p in procs:
                p.wait(timeout=30)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
