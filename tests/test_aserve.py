"""asyncserve parity + continuous-batching proofs (io/aserve).

The async engine must speak the threaded engine's full contract — same
builder, same metric families, same debug routes, deadline / shed /
drain semantics, failpoints — AND prove the behavior that justifies its
existence: a late-arriving request joins the already-forming device
batch (admitted mid-window, served in the next dispatch), co-batched
replies are never cross-wired, and the scoring call reads a pre-pinned
slot-table view instead of materializing a fresh batch array.
"""

import json
import sys
import threading
import time
import http.client
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mmlspark_tpu.io import aserve
from mmlspark_tpu.io.aserve import (AsyncServingQuery, AsyncServingServer,
                                    SlotTable, resolve_engine)
from mmlspark_tpu.io.aserve.server import RowSpec
from mmlspark_tpu.io.serving import DEBUG_ROUTES, ServingQuery, serve
from mmlspark_tpu.observability import flight, metrics
from mmlspark_tpu.robustness import failpoints, policy

TRACE_ID = "c" * 32
TRACEPARENT = f"00-{TRACE_ID}-{'d' * 16}-01"


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


def _request(host, port, path, body=None, headers=None, timeout=30,
             method=None):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    if isinstance(body, str):
        body = body.encode()
    conn.request(method or ("POST" if body is not None else "GET"),
                 path, body=body, headers=headers or {})
    r = conn.getresponse()
    payload = r.read()
    hdrs = dict(r.getheaders())
    conn.close()
    return r.status, payload, hdrs


def _echo_transform(ds):
    return ds.with_column("reply", [
        {"entity": {"i": (v or {}).get("i")}, "statusCode": 200}
        for v in ds["value"]])


def _echo_query(api="ares", **kw):
    server = AsyncServingServer("localhost", 0, api, **kw)
    return AsyncServingQuery(server, transform=_echo_transform).start()


# ---------------------------------------------------------------------------
# Engine selection
# ---------------------------------------------------------------------------


class TestEngineSelection:
    def test_explicit_and_default(self):
        assert resolve_engine("async") == "async"
        assert resolve_engine("threaded") == "threaded"
        # the async engine is the default (ROADMAP item 1 first step)
        assert resolve_engine(None) == "async"
        assert aserve.DEFAULT_ENGINE == "async"
        with pytest.raises(ValueError):
            resolve_engine("uvloop")

    def test_env_selects_async(self, monkeypatch):
        monkeypatch.setenv(aserve.ENGINE_ENV, "async")
        assert resolve_engine(None) == "async"
        q = serve().address("localhost", 0, "envsel").transform(
            _echo_transform).start()
        try:
            assert isinstance(q, AsyncServingQuery)
        finally:
            q.stop()

    def test_bad_env_degrades_async_with_flight_event(self, monkeypatch):
        monkeypatch.setenv(aserve.ENGINE_ENV, "turbo")
        assert resolve_engine(None) == "async"
        assert any(e["kind"] == "serving_engine"
                   and e["decision"] == "fallback_async"
                   for e in flight.events())

    def test_threaded_selection_is_deprecated(self, monkeypatch):
        """Explicit threaded selection (arg or env) still works but
        leaves a deprecation counter per selection path."""
        def count(source):
            return metrics.counter("serving_engine_deprecated_total",
                                   engine="threaded",
                                   source=source).value

        before = count("explicit")
        assert resolve_engine("threaded") == "threaded"
        assert count("explicit") == before + 1
        monkeypatch.setenv(aserve.ENGINE_ENV, "threaded")
        before_env = count("env")
        assert resolve_engine(None) == "threaded"
        assert count("env") == before_env + 1
        # the default path stays silent
        monkeypatch.delenv(aserve.ENGINE_ENV, raising=False)
        silent = count("explicit") + count("env")
        assert resolve_engine(None) == "async"
        assert count("explicit") + count("env") == silent

    def test_builder_engine_beats_env(self, monkeypatch):
        monkeypatch.setenv(aserve.ENGINE_ENV, "async")
        q = (serve().address("localhost", 0, "ovr").engine("threaded")
             .transform(_echo_transform).start())
        try:
            assert isinstance(q, ServingQuery)
        finally:
            q.stop()


# ---------------------------------------------------------------------------
# Slot table
# ---------------------------------------------------------------------------


class TestSlotTable:
    def test_pow2_rounding_and_width_check(self):
        t = SlotTable(6, 3)
        assert t.slots == 8
        assert SlotTable(32, 1).slots == 32
        t.write(0, [1, 2, 3])
        with pytest.raises(ValueError):
            t.write(0, [1, 2])

    def test_flip_ping_pongs_without_copies(self):
        t = SlotTable(4, 2)
        a = t.forming
        t.write(0, [1.0, 2.0])
        dispatched = t.flip()
        assert dispatched is a                  # handed over, not copied
        assert t.forming is not a               # loop now fills the twin
        assert dispatched[0].tolist() == [1.0, 2.0]

    def test_bucket_view_pads_with_row0(self):
        t = SlotTable(8, 2)
        buf = t.forming
        buf[:3] = [[1, 1], [2, 2], [3, 3]]
        buf[3:] = 99.0                          # stale bytes from batch N-1
        view, bucket = SlotTable.bucket_view(buf, 3)
        assert bucket == 4 and view.shape == (4, 2)
        assert view[3].tolist() == [1.0, 1.0]   # pad = row 0, never stale
        assert np.shares_memory(view, buf)

    def test_env_slot_override(self, monkeypatch):
        from mmlspark_tpu.io.aserve.slots import resolve_slots
        assert resolve_slots(32) == 32
        monkeypatch.setenv("MMLSPARK_TPU_ASERVE_SLOTS", "6")
        assert resolve_slots(32) == 8           # pow2-rounded override
        monkeypatch.setenv("MMLSPARK_TPU_ASERVE_SLOTS", "0")
        assert resolve_slots(16) == 16


# ---------------------------------------------------------------------------
# Continuous batching: the behavioral acceptance
# ---------------------------------------------------------------------------


class TestWireHardening:
    def test_oversized_header_line_answers_431(self):
        """An over-limit line raises ValueError out of readline (asyncio
        converts LimitOverrunError) — it must answer 431, not drop the
        connection with an unhandled task exception."""
        import socket as socketlib

        q = _echo_query("hard")
        try:
            with socketlib.create_connection(
                    (q.server.host, q.server.port), timeout=10) as s:
                s.sendall(b"POST /hard HTTP/1.1\r\n"
                          b"X-Big: " + b"a" * 80_000 + b"\r\n\r\n")
                reply = s.recv(4096)
            assert reply.startswith(b"HTTP/1.1 431"), reply[:80]
        finally:
            q.stop()

    def test_failed_bind_keeps_failing_loudly(self):
        import socket as socketlib

        blocker = socketlib.socket()
        blocker.bind(("localhost", 0))
        port = blocker.getsockname()[1]
        server = AsyncServingServer("localhost", port, "bindfail")
        try:
            with pytest.raises(RuntimeError):
                server.start()
            # the retry must run the bind again and fail loudly — not
            # silently no-op against a dead instance
            with pytest.raises(RuntimeError):
                server.start()
        finally:
            blocker.close()
            server.stop()


class TestContinuousBatching:
    def test_late_arrival_joins_forming_batch(self):
        """While the device is busy with batch N, later requests are
        admitted mid-window and served together in dispatch N+1 — the
        defining difference from fixed get_batch windows."""
        gate = threading.Event()
        first_scored = threading.Event()
        batch_sizes = []

        def transform(ds):
            batch_sizes.append(len(list(ds["id"])))
            if not first_scored.is_set():
                first_scored.set()
                assert gate.wait(10)
            return _echo_transform(ds)

        server = AsyncServingServer("localhost", 0, "cb")
        q = AsyncServingQuery(server, transform=transform).start()
        results = {}

        def post(i):
            status, body, _ = _request(server.host, server.port, "/cb",
                                       json.dumps({"i": i}))
            results[i] = (status, json.loads(body))

        try:
            t1 = threading.Thread(target=post, args=(1,))
            t1.start()
            assert first_scored.wait(10)        # request 1 on the device
            late = [threading.Thread(target=post, args=(i,))
                    for i in (2, 3)]
            for t in late:
                t.start()
            # both late arrivals are admitted into the FORMING batch
            deadline = time.monotonic() + 5
            while server.backlog() < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server.backlog() == 2
            gate.set()
            for t in [t1] + late:
                t.join(timeout=15)
            assert results == {1: (200, {"i": 1}), 2: (200, {"i": 2}),
                               3: (200, {"i": 3})}, results
            # 3 requests, exactly 2 device dispatches: [1] then [2, 3]
            assert batch_sizes == [1, 2], batch_sizes
            # the counter increments on the scoring thread AFTER replies
            # are posted to the loop — give it the scheduler tick it
            # needs under parallel-suite load
            deadline = time.monotonic() + 5
            while q.batches_served < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert q.batches_served == 2
        finally:
            q.stop()

    def test_no_cross_wiring_under_concurrency(self):
        def transform(ds):
            # a non-instant score (a real model's shape): arrivals pile
            # into the forming batch while the "device" is busy, so
            # continuous batching has something to prove
            time.sleep(0.002)
            return _echo_transform(ds)

        server = AsyncServingServer("localhost", 0, "wire")
        q = AsyncServingQuery(server, transform=transform).start()
        errs = []

        def client(base):
            try:
                conn = http.client.HTTPConnection(q.server.host,
                                                  q.server.port,
                                                  timeout=15)
                for k in range(25):
                    i = base * 1000 + k
                    conn.request("POST", "/wire",
                                 body=json.dumps({"i": i}))
                    r = conn.getresponse()
                    body = json.loads(r.read())
                    if r.status != 200 or body != {"i": i}:
                        errs.append((i, r.status, body))
                conn.close()
            except Exception as e:  # noqa: BLE001 — a failure IS the signal
                errs.append(repr(e))

        threads = [threading.Thread(target=client, args=(b,))
                   for b in range(6)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not errs, errs[:5]
            # the scorer counts a batch after it has replied: the last
            # client can be here before the last count
            q.await_served(150, timeout=5)
            assert q.requests_served == 150
            # under 6 concurrent keep-alive clients batching must form
            assert q.batches_served < q.requests_served
        finally:
            q.stop()


# ---------------------------------------------------------------------------
# Zero-copy rows mode
# ---------------------------------------------------------------------------


class TestRowsMode:
    def test_scorer_sees_slot_table_views(self):
        seen = []

        def scorer(X):
            seen.append(X)
            return X.sum(axis=1)

        server = AsyncServingServer(
            "localhost", 0, "rows", slots=8,
            row_spec=RowSpec(4, extract="x"))
        q = AsyncServingQuery(server, scorer=scorer,
                              reply_fn=lambda r, p: {"y": float(p)}
                              ).start()
        try:
            for i in range(3):
                status, body, _ = _request(
                    server.host, server.port, "/rows",
                    json.dumps({"x": [i, 1.0, 2.0, 3.0]}))
                assert status == 200
                assert json.loads(body) == {"y": i + 6.0}
            assert seen
            for view in seen:
                assert any(np.shares_memory(view, b)
                           for b in server.slot_table._bufs), \
                    "scoring call did not read the pre-pinned staging"
            # the staging decision is observable
            assert any(e["kind"] == "placement"
                       and e.get("site") == "aserve.slots"
                       for e in flight.events())
        finally:
            q.stop()

    def test_bad_rows_answer_400_not_crash(self):
        server = AsyncServingServer("localhost", 0, "badrows", slots=4,
                                    row_spec=RowSpec(3, extract="x"))
        q = AsyncServingQuery(server, scorer=lambda X: X.sum(axis=1)
                              ).start()
        try:
            status, body, _ = _request(server.host, server.port,
                                       "/badrows", b'{"x": [1, 2]}')
            assert status == 400 and b"features" in body
            status, body, _ = _request(server.host, server.port,
                                       "/badrows", b'not json')
            assert status == 400
            # the plane survives: a good row still scores
            status, body, _ = _request(server.host, server.port,
                                       "/badrows", b'{"x": [1, 2, 3]}')
            assert status == 200
            # exact-count parity: both 400s counted AS 400s (a bad-json
            # reply must not masquerade as a 504 in the exposition)
            assert metrics.counter("serving_responses_total",
                                   api="badrows",
                                   code="400").value == 2.0
            assert metrics.counter("serving_responses_total",
                                   api="badrows",
                                   code="504").value == 0.0
        finally:
            q.stop()

    def test_booster_in_the_loop(self):
        """The real zero-copy target: a compiled fused predictor scoring
        slot-table views — one h2d per dispatch, predictions match the
        direct predict() path bit-for-bit."""
        from tests.test_predict_device import make_booster

        b = make_booster(T=4, K=1, F=4)
        server = AsyncServingServer(
            "localhost", 0, "model", slots=8,
            row_spec=RowSpec(4, extract="features"))
        q = AsyncServingQuery(
            server, scorer=b.predict,
            reply_fn=lambda r, p: {"p": float(p)}).start()
        try:
            rng = np.random.default_rng(3)
            X = rng.normal(size=(5, 4)).astype(np.float32)
            want = b.predict(X)
            for i in range(5):
                status, body, _ = _request(
                    server.host, server.port, "/model",
                    json.dumps({"features": X[i].tolist()}))
                assert status == 200
                got = json.loads(body)["p"]
                assert got == pytest.approx(float(want[i]), abs=1e-6)
        finally:
            q.stop()


# ---------------------------------------------------------------------------
# Parity: shed / deadline / drain / tracing / debug routes
# ---------------------------------------------------------------------------


class TestAdmissionParity:
    def test_bounded_queue_sheds_429_with_retry_after(self):
        gate = threading.Event()
        scoring = threading.Event()

        def transform(ds):
            scoring.set()
            assert gate.wait(15)
            return _echo_transform(ds)

        # capacity while the device is held: 1 dispatched + 1 forming
        # + 1 pending — the FOURTH request must shed
        server = AsyncServingServer("localhost", 0, "shed", slots=1,
                                    max_queue_depth=1)
        q = AsyncServingQuery(server, transform=transform).start()
        results = []

        def post(i):
            results.append(_request(server.host, server.port, "/shed",
                                    json.dumps({"i": i})))

        try:
            threads = [threading.Thread(target=post, args=(i,))
                       for i in range(3)]
            threads[0].start()
            assert scoring.wait(10)          # request 0 holds the device
            deadline = time.monotonic() + 5
            threads[1].start()               # -> forming slot
            while server.backlog() < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            threads[2].start()               # -> pending (bound = 1)
            while server.backlog() < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert server.backlog() == 2
            status, body, hdrs = _request(server.host, server.port,
                                          "/shed", b'{"i": 9}')
            assert status == 429, body
            assert int(hdrs["Retry-After"]) >= 1
            assert metrics.counter("serving_shed_total", api="shed",
                                   reason="queue_full").value == 1.0
            assert any(e["kind"] == "shed" for e in flight.events())
            gate.set()
            for t in threads:
                t.join(timeout=15)
            assert sorted(r[0] for r in results) == [200, 200, 200]
        finally:
            gate.set()
            q.stop()

    def test_expired_deadline_rejected_at_admission(self):
        q = _echo_query("dl")
        try:
            status, _, _ = _request(q.server.host, q.server.port, "/dl",
                                    b'{"i": 1}',
                                    headers={policy.DEADLINE_HEADER: "0"})
            assert status == 504
            assert metrics.counter("serving_deadline_dropped_total",
                                   api="dl", stage="admission").value == 1.0
        finally:
            q.stop()

    def test_batch_stage_drops_expired_cobatched(self):
        """A request whose deadline expires while it waits behind a slow
        batch is dropped pre-dispatch (504, stage=batch) instead of
        spending device time on a reply nobody awaits."""
        gate = threading.Event()
        first_scored = threading.Event()

        def transform(ds):
            if not first_scored.is_set():
                first_scored.set()
                assert gate.wait(10)
            return _echo_transform(ds)

        server = AsyncServingServer("localhost", 0, "dldrop")
        q = AsyncServingQuery(server, transform=transform).start()
        try:
            t1 = threading.Thread(target=_request, args=(
                server.host, server.port, "/dldrop", b'{"i": 1}'))
            t1.start()
            assert first_scored.wait(10)
            # deadline shorter than the gate hold: expires in-queue
            status, _, _ = _request(server.host, server.port, "/dldrop",
                                    b'{"i": 2}',
                                    headers={policy.DEADLINE_HEADER:
                                             "300"})
            assert status == 504
            gate.set()
            t1.join(timeout=15)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                c = metrics.counter("serving_deadline_dropped_total",
                                    api="dldrop", stage="batch")
                if c.value >= 1.0:
                    break
                time.sleep(0.02)
            assert metrics.counter("serving_deadline_dropped_total",
                                   api="dldrop",
                                   stage="batch").value == 1.0
            assert any(e["kind"] == "deadline_dropped"
                       for e in flight.events())
        finally:
            gate.set()
            q.stop()

    def test_drain_refuses_new_finishes_admitted(self):
        q = _echo_query("drain")
        host, port = q.server.host, q.server.port
        status, _, _ = _request(host, port, "/drain", b'{"i": 1}')
        assert status == 200
        q.server.begin_drain()
        status, body, hdrs = _request(host, port, "/drain", b'{"i": 2}')
        assert status == 503 and b"draining" in body
        assert "Retry-After" in hdrs
        assert metrics.counter("serving_shed_total", api="drain",
                               reason="draining").value == 1.0
        stats = q.drain(settle_seconds=0, timeout=5)
        assert stats["clean"] is True
        assert stats["requests_served"] == 1
        assert any(e["kind"] == "drain_complete"
                   for e in flight.events())


class TestTracingParity:
    def test_request_id_echo_and_trace_adoption(self):
        q = _echo_query("trc")
        try:
            status, _, hdrs = _request(
                q.server.host, q.server.port, "/trc", b'{"i": 1}',
                headers={"traceparent": TRACEPARENT})
            assert status == 200
            assert hdrs["X-Request-Id"] == TRACE_ID
        finally:
            q.stop()

    def test_one_trace_id_edge_gateway_async_worker(self):
        """The gateway is engine-transparent: async workers behind it
        keep the one-trace-id contract (edge -> gateway -> worker) and
        the deadline attenuation."""
        from mmlspark_tpu.io.distributed_serving import DistributedServing

        def transform(ds):
            return ds.with_column("reply", [
                {"entity": {"i": (v or {}).get("i"),
                            "deadline": h.get("x-deadline-ms")},
                 "statusCode": 200}
                for h, v in zip(ds["headers"], ds["value"])])

        d = DistributedServing(transform, num_workers=2,
                               engine="async").start()
        try:
            for k in range(8):
                status, body, hdrs = _request(
                    d.gateway.host, d.gateway.port, "/serving",
                    json.dumps({"i": k}),
                    headers={"traceparent": TRACEPARENT,
                             policy.DEADLINE_HEADER: "8000"})
                assert status == 200
                reply = json.loads(body)
                assert reply["i"] == k
                assert 5000.0 < float(reply["deadline"]) < 8000.0
                assert hdrs["X-Request-Id"] == TRACE_ID
            served = [q.requests_served for q in d.workers]
            assert sum(served) == 8
        finally:
            d.stop()


class TestDebugRoutes:
    def test_all_routes_answer_in_band(self):
        q = _echo_query("dbg")
        host, port = q.server.host, q.server.port
        try:
            # one real request first: the exposition needs families
            status, _, _ = _request(host, port, "/dbg", b'{"i": 1}')
            assert status == 200
            status, body, _ = _request(host, port, "/metrics")
            assert status == 200 and b"# TYPE" in body
            status, body, _ = _request(host, port, "/healthz")
            assert status == 200 and json.loads(body)["status"] == "ok"
            status, body, _ = _request(host, port, "/varz")
            assert status == 200
            assert json.loads(body)["config"]["api_name"] == "dbg"
            status, body, _ = _request(host, port, "/debug/flight")
            assert status == 200 and isinstance(json.loads(body), dict)
            # the /{api} alias works like the threaded engine's
            status, body, _ = _request(host, port, "/dbg/healthz")
            assert status == 200
        finally:
            q.stop()

    def test_engine_metric_family_and_route_parity(self):
        """Drift guard (PR 13 found a double-count bug exactly this
        way): identical traffic through both engines must surface the
        identical set of metric FAMILY names on /metrics, and every
        DEBUG_ROUTES path must answer 200 on both."""
        import re as _re

        def drive(engine):
            q = (serve().address("localhost", 0, "par").batch(8, 5)
                 .engine(engine).transform(_echo_transform).start())
            host, port = q.server.host, q.server.port
            try:
                # families accumulated from traffic only — boot-time
                # one-offs (engine deprecation counters) are not part
                # of the request-plane contract
                metrics.reset()
                status, _, _ = _request(host, port, "/par", b'{"i": 1}')
                assert status == 200
                routes = {}
                for name, path in DEBUG_ROUTES:
                    status, _, _ = _request(host, port, path)
                    routes[name] = status
                status, body, _ = _request(host, port, "/metrics")
                assert status == 200
                fams = set(_re.findall(r"^# TYPE ([a-z_]+) ",
                                       body.decode(), _re.M))
            finally:
                q.stop()
            return fams, routes

        t_fams, t_routes = drive("threaded")
        a_fams, a_routes = drive("async")
        ok = {name: 200 for name, _ in DEBUG_ROUTES}
        assert t_routes == ok and a_routes == ok, (t_routes, a_routes)
        assert t_fams == a_fams, \
            f"family drift between engines: {sorted(t_fams ^ a_fams)}"

    def test_disabled_metrics_reclaims_the_path(self):
        q = _echo_query("off")
        try:
            metrics.set_enabled(False)
            status, body, _ = _request(q.server.host, q.server.port,
                                       "/metrics")
            # normal traffic now: the echo transform answers, not the
            # exposition (the kill-switch contract)
            assert b"# TYPE" not in body
        finally:
            metrics.set_enabled(True)
            q.stop()


# ---------------------------------------------------------------------------
# Failpoints: seeded chaos on the async plane
# ---------------------------------------------------------------------------


class TestFailpointsParity:
    def test_injected_503_then_recovery(self):
        failpoints.configure("serving.handle:error_503@1", seed=7)
        q = _echo_query("chaos")
        try:
            status, body, _ = _request(q.server.host, q.server.port,
                                       "/chaos", b'{"i": 0}')
            assert status == 503 and b"injected" in body
            status, body, _ = _request(q.server.host, q.server.port,
                                       "/chaos", b'{"i": 1}')
            assert status == 200 and json.loads(body) == {"i": 1}
            assert metrics.counter("failpoints_fired_total",
                                   site="serving.handle",
                                   kind="error_503").value == 1.0
            assert any(e["kind"] == "failpoint"
                       and e["site"] == "serving.handle"
                       for e in flight.events())
        finally:
            q.stop()

    def test_batch_error_rides_requeue_once(self):
        failpoints.configure("serving.batch:error@1", seed=7)
        q = _echo_query("requeue")
        try:
            status, body, _ = _request(q.server.host, q.server.port,
                                       "/requeue", b'{"i": 5}',
                                       timeout=15)
            # crash on the first dispatch, requeued, served on the retry
            assert status == 200 and json.loads(body) == {"i": 5}
            assert metrics.counter("serving_batch_failures_total",
                                   api="requeue").value == 1.0
            assert metrics.counter("serving_requeues_total",
                                   api="requeue").value == 1.0
            assert any(e["kind"] == "batch_error"
                       for e in flight.events())
        finally:
            q.stop()

    def test_persistent_crash_answers_500_after_one_requeue(self):
        def transform(ds):
            raise RuntimeError("boom")

        server = AsyncServingServer("localhost", 0, "boom")
        q = AsyncServingQuery(server, transform=transform).start()
        try:
            status, body, _ = _request(server.host, server.port, "/boom",
                                       b'{"i": 1}', timeout=15)
            assert status == 500 and b"internal" in body
            assert metrics.counter("serving_batch_failures_total",
                                   api="boom").value >= 2.0
        finally:
            q.stop()

    def test_seeded_replay_is_deterministic(self):
        def pattern(seed):
            failpoints.configure("serving.handle:error_503:0.5",
                                 seed=seed)
            out = [failpoints.fault_point("serving.handle") is not None
                   for _ in range(64)]
            failpoints.clear()
            return out

        assert pattern(13) == pattern(13)
        assert pattern(13) != pattern(14)
