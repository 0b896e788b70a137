"""Serving latency proof vs the reference's ~1 ms continuous-serving claim.

Reference: docs/mmlspark-serving.md:10-11 ("millisecond latency" for Spark
Serving continuous mode, HTTPSourceV2.scala:45-700). This measures true
end-to-end HTTP p50/p99 over loopback against a persistent compiled program:

* idle load (sequential requests): with eager batching a lone request must
  NOT pay the micro-batch deadline — p50 is the transform cost, single-digit
  ms on a 1-core CI box.
* concurrent load: batches must actually form (batches_served <<
  requests_served), or the MXU would see batch-1 shapes under load.

CI bounds are deliberately loose multiples of the target (shared boxes jitter);
bench.py records the tight numbers on the bench host.
"""

import http.client
import threading

import time

import numpy as np

from mmlspark_tpu.io.serving import serve


def _measure(host, port, path, n, payload=b'{"x": 1.0}'):
    lat = []
    conn = http.client.HTTPConnection(host, port, timeout=10)
    for _ in range(n):
        t0 = time.perf_counter()
        conn.request("POST", path, body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        lat.append(time.perf_counter() - t0)
        assert resp.status == 200
    conn.close()
    return np.asarray(lat) * 1e3  # ms


def serving_latency_stats(n_seq=200, n_conc=8, conc_each=50,
                          engine=None):
    """Start a trivial-model serving query, return latency stats (ms).
    ``engine`` picks the serving engine (None = env default) — bench.py
    measures both in one round for the threaded-vs-async A/B."""

    def transform(ds):
        vals = ds["value"]
        return ds.with_column(
            "reply", [{"entity": {"y": (v or {}).get("x", 0.0)},
                       "statusCode": 200} for v in vals])

    b = (serve().address("localhost", 0, "bench")
         .batch(max_batch=64, max_latency_ms=5)
         .transform(transform))
    if engine is not None:
        b = b.engine(engine)
    q = b.start()
    host, port = q.server.host, q.server.port
    path = "/bench"
    try:
        _measure(host, port, path, 20)              # warm
        seq = _measure(host, port, path, n_seq)

        results = []
        def worker():
            results.append(_measure(host, port, path, conc_each))
        threads = [threading.Thread(target=worker) for _ in range(n_conc)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        conc = np.concatenate(results)
        stats = {
            "p50_ms": float(np.percentile(seq, 50)),
            "p99_ms": float(np.percentile(seq, 99)),
            "concurrent_p50_ms": float(np.percentile(conc, 50)),
            "concurrent_p99_ms": float(np.percentile(conc, 99)),
            "concurrent_rps": float(n_conc * conc_each / wall),
            "batches_served": q.batches_served,
            "requests_served": q.requests_served,
        }
        return stats
    finally:
        q.stop()


def serving_model_latency_stats(n_seq=100, n_conc=4, conc_each=25):
    """Latency with a compiled GBDT booster scoring every micro-batch — the
    accelerator-in-loop number the host-only proof cannot give. On TPU this
    includes the host->device->host hop; on CPU it measures the serving
    stack + jitted predict. Batches are padded to the
    fixed max_batch shape so the compiled program never re-specializes."""
    from mmlspark_tpu.models.gbdt.booster import train_booster
    from mmlspark_tpu.models.gbdt.growth import GrowConfig

    rng = np.random.default_rng(0)
    F, max_batch = 8, 64
    Xtr = rng.normal(size=(2000, F)).astype(np.float32)
    ytr = (Xtr[:, 0] + Xtr[:, 1] > 0).astype(np.float32)
    booster = train_booster(Xtr, ytr, objective="binary", num_iterations=10,
                            cfg=GrowConfig(num_leaves=15), max_bin=63)
    pad = np.zeros((max_batch, F), np.float32)

    def transform(ds):
        vals = ds["value"]
        X = pad.copy()
        for i, v in enumerate(vals[:max_batch]):
            X[i] = np.asarray((v or {}).get("x", [0.0] * F), np.float32)
        preds = booster.predict(X)[:len(vals)]
        return ds.with_column(
            "reply", [{"entity": {"y": float(p)}, "statusCode": 200}
                      for p in preds])

    q = (serve().address("localhost", 0, "bench_model")
         .batch(max_batch=max_batch, max_latency_ms=5)
         .transform(transform).start())
    host, port = q.server.host, q.server.port
    path = "/bench_model"
    payload = (b'{"x": [' + b", ".join(b"0.5" for _ in range(F)) + b']}')
    try:
        _measure(host, port, path, 20, payload=payload)      # warm/compile
        seq = _measure(host, port, path, n_seq, payload=payload)
        results = []

        def worker():
            results.append(_measure(host, port, path, conc_each,
                                    payload=payload))
        threads = [threading.Thread(target=worker) for _ in range(n_conc)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {
            "p50_ms": float(np.percentile(seq, 50)),
            "p99_ms": float(np.percentile(seq, 99)),
            "concurrent_rps": float(n_conc * conc_each / wall),
            "batches_served": q.batches_served,
            "requests_served": q.requests_served,
        }
    finally:
        q.stop()


def serving_async_model_latency_stats(predict_dtype=None, n_seq=100,
                                      n_conc=4, conc_each=25):
    """Async-engine model-in-loop latency on the zero-copy rows path —
    requests decode straight into the slot table (quantized to the
    lane's staging dtype when ``predict_dtype`` resolves to int8/bf16)
    and the booster scores slot views with the matching predictor lane.
    This is the serving configuration ``serving_main`` builds for a
    booster model, so the bench's int8-admission rps comes from the
    same code path production runs."""
    from mmlspark_tpu.io.aserve import AsyncServingQuery, AsyncServingServer
    from mmlspark_tpu.io.aserve.server import RowSpec
    from mmlspark_tpu.models.gbdt import quantize
    from mmlspark_tpu.models.gbdt.booster import train_booster
    from mmlspark_tpu.models.gbdt.growth import GrowConfig

    rng = np.random.default_rng(0)
    F, max_batch = 8, 64
    Xtr = rng.normal(size=(2000, F)).astype(np.float32)
    ytr = (Xtr[:, 0] + Xtr[:, 1] > 0).astype(np.float32)
    booster = train_booster(Xtr, ytr, objective="binary", num_iterations=10,
                            cfg=GrowConfig(num_leaves=15), max_bin=63)
    pdt = booster.resolved_predict_dtype(predict_dtype)
    quantizer = quantize.row_quantizer(
        pdt, quantize.feature_bounds(booster.binner_state)
        if pdt == "int8" else None)
    server = AsyncServingServer(
        "localhost", 0, "bench_rows", slots=max_batch,
        row_spec=RowSpec(F, extract="features",
                         dtype=quantize.staging_dtype(pdt),
                         quantizer=quantizer))
    q = AsyncServingQuery(
        server, scorer=lambda X: booster.predict(X, predict_dtype=pdt),
        reply_fn=lambda req, p: {"y": float(p)}).start()
    host, port = q.server.host, q.server.port
    path = "/bench_rows"
    payload = (b'{"features": ['
               + b", ".join(b"0.5" for _ in range(F)) + b']}')
    try:
        _measure(host, port, path, 20, payload=payload)      # warm/compile
        seq = _measure(host, port, path, n_seq, payload=payload)
        results = []

        def worker():
            results.append(_measure(host, port, path, conc_each,
                                    payload=payload))
        threads = [threading.Thread(target=worker) for _ in range(n_conc)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {
            "p50_ms": float(np.percentile(seq, 50)),
            "p99_ms": float(np.percentile(seq, 99)),
            "concurrent_rps": float(n_conc * conc_each / wall),
            "predict_dtype": pdt,
        }
    finally:
        q.stop()


def flaky(retries: int = 3):
    """Retry decorator for timing-sensitive tests (reference: the Flaky /
    TimeLimitedFlaky traits, core/test/base/TestBase.scala:43-72 — whole-test
    auto-retry rather than loosened assertions). Lives here, not conftest:
    bench.py imports this module outside pytest, where conftest isn't
    importable."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            for attempt in range(retries):
                try:
                    return fn(*args, **kwargs)
                except AssertionError:
                    if attempt == retries - 1:
                        raise
                    time.sleep(0.5 * (attempt + 1))

        return run

    return deco


@flaky(retries=3)
def test_sequential_latency_does_not_pay_batch_deadline():
    stats = serving_latency_stats(n_seq=150, n_conc=4, conc_each=25)
    # reference regime is ~1 ms; allow a loose CI multiple but a lone request
    # must clearly undercut request-rate * deadline behavior (5 ms deadline
    # + transform would push p50 over ~6 ms)
    assert stats["p50_ms"] < 5.0, stats
    assert stats["p99_ms"] < 50.0, stats
    # under concurrency, batching must actually batch
    assert stats["batches_served"] < stats["requests_served"], stats


@flaky(retries=3)
def test_model_in_loop_serving():
    stats = serving_model_latency_stats(n_seq=40, n_conc=2, conc_each=10)
    # CI box: just prove the compiled-predict path serves correctly and
    # batches form; tight numbers come from the bench host
    assert stats["p99_ms"] < 500.0, stats
    assert stats["batches_served"] <= stats["requests_served"], stats


if __name__ == "__main__":
    print(serving_latency_stats())
    print(serving_model_latency_stats())
