"""The quickest proof that the main path still starts on the chip.

    python chip_smoke.py                       # on a TPU; anything else fails
    JAX_PLATFORMS=cpu python chip_smoke.py --toy   # CPU rehearsal, tiny size

One process, no children (a chip belongs to one process at a time). It drives
the path the README leads with — ``LightGBMClassifier.fit`` ->
``model.transform`` -> the async serving engine with that booster in the loop
— at the full width of the model the repo benches (1,000,000 x 28 float32
rows from a seed, 255 bins, 31 leaves, binary objective), and checks every
result by the repo's own means. It fails (non-zero exit, traceback) at the
first thing that goes wrong: there is no ``except`` around a phase.

It measures nothing. The per-phase seconds and compile counts it prints are
set-up facts for whoever reads the log, stamped with the device they came
from; they are not rates and are written nowhere as such: the summary line
that carries them ends with ``"claim": null``. The last line of standard
output is the chip check's result and nothing else, one JSON object with
exactly these keys: ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``; ``--toy`` never prints it.
"""

from __future__ import annotations

import argparse
import contextlib
import http.client
import importlib.metadata
import json
import os
import sys
import tempfile
import threading
import time

ENGINE_ENV = ("MMLSPARK_TPU_PALLAS_INTERPRET", "MMLSPARK_TPU_DISABLE_PALLAS_HIST",
              "MMLSPARK_TPU_HIST_ENGINE")


def _check_engine_env(toy: bool) -> None:
    """Nothing may stand between the path and the Mosaic kernel."""
    overrides = {name: os.environ[name].strip() for name in ENGINE_ENV
                 if os.environ.get(name, "").strip()}
    if overrides.get("MMLSPARK_TPU_HIST_ENGINE", "").lower() == "auto":
        del overrides["MMLSPARK_TPU_HIST_ENGINE"]
    if toy:                             # the rehearsal turns it on itself
        overrides.pop("MMLSPARK_TPU_PALLAS_INTERPRET", None)
    if overrides:
        raise RuntimeError(
            f"{overrides} set: chip_smoke.py checks the default engine "
            "selection and refuses to start with an override")


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


class Report:
    """Per-phase wall seconds and compile counters (deltas over the phase)."""

    COUNTERS = ("gbdt_compiles_total", "gbdt_program_builds_total",
                "persistent_compile_cache_hits_total",
                "persistent_compile_cache_misses_total")

    def __init__(self, stamp: str):
        self.stamp = stamp
        self.phases = {}

    def _totals(self) -> dict:
        return {name: sum(s["value"] for s in _series(name))
                for name in self.COUNTERS}

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"== {name} ==", flush=True)
        before, t0 = self._totals(), time.perf_counter()
        yield
        after = self._totals()
        facts = {"seconds": round(time.perf_counter() - t0, 1)}
        facts.update({k: int(after[k] - before[k]) for k in after})
        self.phases[name] = facts
        print(f"-- {name} passed [{self.stamp}] set-up facts, not rates: "
              f"{json.dumps(facts)}", flush=True)


def _series(family: str) -> list:
    from mmlspark_tpu.observability import metrics
    return (metrics.get_registry().snapshot().get(family) or {}).get(
        "series", [])


def _label_counts(name: str, label: str, values: tuple) -> dict:
    out = dict.fromkeys(values, 0)
    for s in _series(name):
        out[s["labels"][label]] += int(s["value"])
    return out


def engine_counts() -> dict:
    return _label_counts("hist_engine_selected_total", "engine",
                         ("pallas", "onehot", "scatter"))


def layout_counts() -> dict:
    return _label_counts("hist_kernel_layout_total", "layout",
                         ("folded", "plain"))


def onehot_counts() -> dict:
    return _label_counts("hist_kernel_onehot_total", "onehot",
                         ("packed", "compare"))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_gate(toy: bool) -> dict:
    import jax
    import jaxlib

    from mmlspark_tpu import native
    from mmlspark_tpu.observability import roofline
    from mmlspark_tpu.parallel import mesh as meshlib
    from mmlspark_tpu.utils import compile_cache

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print(f"platform: {d0.platform}\ndevice_kind: {d0.device_kind}\n"
          f"device count: {len(devices)}", flush=True)
    print(f"jax {jax.__version__} / jaxlib {jaxlib.__version__} / "
          f"libtpu {_version('libtpu')}")
    want = "cpu" if toy else "tpu"
    if d0.platform != want:
        raise RuntimeError(
            f"chip_smoke.py needs platform {want!r}, jax found "
            f"{d0.platform!r} ({d0.device_kind}); there is no fallback"
            + ("" if toy else " — for a CPU rehearsal pass --toy under "
                              "JAX_PLATFORMS=cpu"))
    print(f"compile cache: {compile_cache.ensure()} "
          f"(from {compile_cache.cache_source()})")
    print(f"native library: {native.lib_path() or 'not loaded (Python fallbacks)'}")
    peaks = roofline.resolve_peaks()
    print(f"roofline peaks: {peaks['source']}")
    if not toy and peaks["source"] != f"table:{d0.device_kind}":
        raise RuntimeError(
            f"device_kind {d0.device_kind!r} has no row in "
            f"roofline._PEAK_TABLE (source={peaks['source']!r})")
    mesh = meshlib.get_default_mesh()
    if mesh.devices.size != len(devices):
        raise RuntimeError(f"default mesh {dict(mesh.shape)} does not take "
                           f"all {len(devices)} visible devices")
    print(f"default mesh: {dict(mesh.shape)}")
    return device


@contextlib.contextmanager
def _scatter_engine():
    """Pin the histogram engine the way a user would, for the reference
    side of the kernel check only."""
    os.environ["MMLSPARK_TPU_HIST_ENGINE"] = "scatter"
    try:
        yield
    finally:
        del os.environ["MMLSPARK_TPU_HIST_ENGINE"]


def kernel_phase(n: int, toy: bool) -> None:
    """Every node_histogram variant the GBDT cells use: Mosaic-compiled,
    and equal to the scatter engine on the same device. Both sides are the
    production entry point under jit on the same f32 stats, so the check
    also holds the engines to one input rounding."""
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.ops.histogram import quantize_stats

    F = 28
    rng = np.random.default_rng(1)
    grad = rng.normal(size=n).astype(np.float32)
    mask = (rng.uniform(size=n) < 0.9).astype(np.float32)
    base = jnp.asarray(np.stack([grad * mask, np.abs(grad) * mask, mask]))
    q, scales = quantize_stats(base)
    for B in (255, 63):
        binned = rng.integers(0, B, size=(F, n), dtype=np.int32)
        for bins_dtype in (np.int32, np.uint8):
            binned_t = jnp.asarray(binned.astype(bins_dtype))
            for W in (1, 16, 31):
                pos = jnp.asarray(rng.integers(-1, W, size=n).astype(np.int32))
                for quantized in (False, True):
                    _kernel_variant(binned_t, pos, q if quantized else base,
                                    scales if quantized else None, W, B, toy)


def _kernel_variant(binned_t, pos, stats, scales, W, B, toy) -> None:
    import jax
    import numpy as np

    from mmlspark_tpu.ops import histogram as H

    F, n = binned_t.shape
    quantized = scales is not None
    tag = (f"B={B} bins={binned_t.dtype} W={W} "
           f"stats={'int8' if quantized else 'bf16'}")

    def lower():
        before = engine_counts()
        lowered = jax.jit(lambda b, p, s: H.node_histogram(
            b, p, s, W, B, scales=scales)).lower(binned_t, pos, stats)
        return lowered, {k: v - before[k]
                         for k, v in engine_counts().items()}

    layouts, onehots = layout_counts(), onehot_counts()
    lowered, picked = lower()
    if picked != {"pallas": 1, "onehot": 0, "scatter": 0}:
        raise RuntimeError(f"{tag}: engine selection {picked}, not pallas")
    layouts = {k: v - layouts[k] for k, v in layout_counts().items()}
    # the rule as ops/histogram.py states it: two 128-lane tiles of bins and
    # both copies of the stats inside one 128-row operand
    layout = "folded" if 128 < B <= 256 and 3 * W <= 64 else "plain"
    if layouts != {"folded": 0, "plain": 0, layout: 1}:
        raise RuntimeError(f"{tag}: kernel layout {layouts}, not {layout}")
    onehots = {k: v - onehots[k] for k, v in onehot_counts().items()}
    # int8 statistics and every value of a tile under 128: the folded tile
    # (bin & 127) or a plain kernel at up to 128 bins
    onehot = ("packed" if quantized and (layout == "folded" or B <= 128)
              else "compare")
    if onehots != {"packed": 0, "compare": 0, onehot: 1}:
        raise RuntimeError(f"{tag}: one-hot build {onehots}, not {onehot}")
    interpret = H._interpret_mode()
    mosaic = "tpu_custom_call" in lowered.as_text()
    if not toy and (interpret or not mosaic):
        raise RuntimeError(f"{tag}: interpret={interpret} mosaic={mosaic} — "
                           "the kernel did not lower through Mosaic")
    got = np.asarray(lowered.compile()(binned_t, pos, stats))
    with _scatter_engine():
        ref, picked = lower()
    if picked != {"pallas": 0, "onehot": 0, "scatter": 1}:
        raise RuntimeError(f"{tag}: reference engine {picked}, not scatter")
    want = np.asarray(ref.compile()(binned_t, pos, stats))
    if got.shape != (F, 3 * W, B):
        raise RuntimeError(f"{tag}: shape {got.shape}")
    # count channel exact; grad/hess to the tolerance
    # tests/test_histogram_engines.py states
    np.testing.assert_array_equal(got[:, 2::3, :], want[:, 2::3, :],
                                  err_msg=tag)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4, err_msg=tag)
    rb = H._pick_row_block(n, F, 3 * W, B, fused_w=W, quantized=quantized)
    print(f"kernel {tag}: engine=pallas interpret={interpret} "
          f"mosaic={mosaic} row_block={rb} layout={layout} onehot={onehot} "
          f"count_exact=True "
          f"max_abs_err={np.abs(got - want).max():.3e}", flush=True)


def train_phase(X, y, iters: int, ndev: int, toy: bool):
    import jax
    import numpy as np

    from mmlspark_tpu.core.dataset import Dataset
    from mmlspark_tpu.models.gbdt.api import LightGBMClassifier
    from mmlspark_tpu.observability import flight
    from mmlspark_tpu.parallel import placement

    ds = Dataset({"features": X, "label": y})
    n_acc = min(len(y), 100_000)
    acc_ds = Dataset({"features": X[:n_acc]})
    first = None
    placement.reset_decision_log()
    for label, extra in (("leafwise (API default)", {}),
                         ("depthwise", {"growthPolicy": "depthwise"}),
                         ("leafwise int8 grad", {"useQuantizedGrad": True})):
        before = engine_counts()
        model = LightGBMClassifier(numIterations=iters, numLeaves=31,
                                   maxBin=255, **extra).fit(ds)
        picked = {k: v - before[k] for k, v in engine_counts().items()}
        if picked["pallas"] <= 0 or picked["onehot"] or picked["scatter"]:
            raise RuntimeError(f"{label}: histogram engines used {picked}")
        pred = np.asarray(model.transform(acc_ds)["prediction"])
        acc = float((pred == y[:n_acc]).mean())
        print(f"fit {label}: {model.booster.num_trees} trees, engines "
              f"{picked}, train accuracy on {n_acc} rows {acc:.4f}",
              flush=True)
        if model.booster.num_trees != iters:
            raise RuntimeError(f"{label}: {model.booster.num_trees} trees")
        if acc <= 0.75:
            raise RuntimeError(f"{label}: train accuracy {acc:.4f} <= 0.75")
        if first is None:
            first = model
            _check_residency(ndev, toy)
    fits = [e for e in flight.events()
            if e["kind"] == "placement" and e.get("site") == "gbdt.fit"]
    if not fits or any(e["nshards"] != ndev for e in fits):
        raise RuntimeError(f"gbdt.fit placement events {fits} do not show "
                           f"nshards == {ndev}")
    print(f"gbdt.fit placement: {fits[-1]['decision']}, nshards={ndev}, "
          f"backend={fits[-1]['backend']}")
    return first


def _check_residency(ndev: int, toy: bool) -> None:
    """The binned dataset is resident (the API caches it across fits): every
    device must hold some of it, not device 0 all of it."""
    import jax

    for d in jax.devices():
        stats = d.memory_stats()
        if stats is None and toy:
            print(f"bytes_in_use on {d}: not reported by the cpu backend")
            continue
        print(f"bytes_in_use on {d}: {stats['bytes_in_use']}")
        if stats["bytes_in_use"] <= 0:
            raise RuntimeError(f"{d} holds nothing while the dataset is "
                               f"resident on a {ndev}-device mesh")


def host_traversal(booster, X):
    """Plain numpy walk of the same trees: raw score [n]."""
    import numpy as np

    t = booster.trees
    rows = np.arange(X.shape[0])
    raw = np.full(X.shape[0], booster.base_score[0], np.float32)
    for k in range(booster.num_trees):
        node = np.zeros(X.shape[0], np.int32)
        for _ in range(booster.depth_cap):
            go_left = ~(X[rows, t.feat[k][node]] > booster.thr_raw[k][node])
            nxt = np.where(go_left, t.left[k][node], t.right[k][node])
            node = np.where(t.is_leaf[k][node], node, nxt)
        raw += t.leaf_value[k][node]
    return raw


def predict_phase(model, X) -> None:
    import numpy as np

    from mmlspark_tpu.core.dataset import Dataset

    booster = model.booster
    out = model.transform(Dataset({"features": X}))
    raw = np.asarray(out["rawPrediction"])[:, 1]
    prob = np.asarray(out["probability"])[:, 1]
    want_raw = host_traversal(booster, X)
    want_prob = 1.0 / (1.0 + np.exp(-want_raw.astype(np.float64)))
    if not (np.isfinite(raw).all() and prob.shape == (X.shape[0],)):
        raise RuntimeError("transform output is not finite [n] scores")
    np.testing.assert_allclose(raw, want_raw, rtol=0, atol=1e-5)
    np.testing.assert_allclose(prob, want_prob, rtol=0, atol=1e-5)
    print(f"transform on {X.shape[0]} rows == host numpy traversal: "
          f"max |d raw| {np.abs(raw - want_raw).max():.2e}, "
          f"max |d prob| {np.abs(prob - want_prob).max():.2e}")
    lane = booster.resolved_predict_dtype("int8")
    if lane != "int8":
        raise RuntimeError(f"int8 predict lane degraded to {lane!r}")
    d8 = np.abs(booster.predict(X, predict_dtype="int8")
                - booster.predict(X)).max()
    print(f"int8 predict lane vs f32: max |d prob| {d8:.4f} (lane documents "
          "< 0.01)")
    if not d8 < 0.01:
        raise RuntimeError(f"int8 lane delta {d8}")


def serve_phase(model, X, platform: str) -> None:
    """The async engine as `serving_main worker --engine async` builds it:
    native .npz booster, rows path, slot table; keep-alive clients."""
    import numpy as np

    from mmlspark_tpu.io import serving_main

    booster = model.booster
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.npz")
        booster.save(path)
        query, served = serving_main._build_async_query(argparse.Namespace(
            model=path, host="localhost", port=0, api_name="serving",
            max_queue_depth=None, max_batch=8, input_col="features",
            output_col="prediction"))
    if query.server.slot_table is None:
        raise RuntimeError("worker did not take the zero-copy rows path")
    lane = served.resolved_predict_dtype()
    query.start()
    try:
        host, port = query.server.host, query.server.port
        conn = http.client.HTTPConnection(host, port, timeout=120)
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        conn.close()
        print(f"/healthz: {health}")
        if health.get("platform") != platform or health.get("status") != "ok":
            raise RuntimeError(f"/healthz does not report {platform!r}")

        n_clients, each = 4, 8
        rows = X[:n_clients * each]
        want = served.predict(rows, predict_dtype=lane)
        got = np.full(len(rows), np.nan)
        errors = []

        def client(c: int) -> None:
            try:
                conn = http.client.HTTPConnection(host, port, timeout=120)
                for i in range(c * each, (c + 1) * each):
                    conn.request(
                        "POST", "/serving",
                        body=json.dumps({"features": rows[i].tolist()}),
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    body = resp.read()
                    if resp.status != 200:
                        raise RuntimeError(f"HTTP {resp.status}: {body!r}")
                    got[i] = json.loads(body)["prediction"]
                conn.close()
            except Exception as e:  # noqa: BLE001 — re-raised on the main thread
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        if errors:
            raise errors[0]
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a serving client did not finish")
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        print(f"{len(rows)} keep-alive requests over {n_clients} "
              f"connections, lane {lane}: every reply == booster.predict "
              f"of its row (max |d| {np.abs(got - want).max():.1e})")
    finally:
        stats = query.drain(settle_seconds=0.0, timeout=30.0)
    print(f"drain: {stats}")
    if not stats["clean"]:
        raise RuntimeError(f"serving engine did not stop cleanly: {stats}")


def several_chips_phase(X, y, iters: int, ndev: int) -> None:
    """hist_blocks=8 pins the reduction geometry: one chip and all chips
    must grow byte-identical models (the README's claim)."""
    import jax

    from mmlspark_tpu.models.gbdt.booster import train_booster
    from mmlspark_tpu.models.gbdt.growth import GrowConfig
    from mmlspark_tpu.parallel.mesh import make_mesh

    if ndev < 2:
        print("one visible device: 1-vs-all-chips identity has nothing to "
              "compare; skipped")
        return
    strings = []
    for devices in (jax.devices()[:1], jax.devices()):
        before = engine_counts()
        booster = train_booster(
            X, y, objective="binary", num_iterations=iters, max_bin=255,
            cfg=GrowConfig(num_leaves=31, hist_blocks=8),
            mesh=make_mesh(devices=devices))
        picked = {k: v - before[k] for k, v in engine_counts().items()}
        if picked["pallas"] <= 0 or picked["onehot"] or picked["scatter"]:
            raise RuntimeError(f"hist_blocks=8 on {len(devices)} device(s): "
                               f"histogram engines used {picked}")
        strings.append(booster.model_string())
        print(f"hist_blocks=8 fit on {len(devices)} device(s): "
              f"{booster.num_trees} trees, engines {picked}", flush=True)
    if strings[0] != strings[1]:
        raise RuntimeError("hist_blocks=8: the 1-device and the "
                           f"{ndev}-device model_string() differ")
    print(f"model_string() byte-identical on 1 and {ndev} devices "
          f"({len(strings[0])} bytes)")


# ---------------------------------------------------------------------------


def result_line(device: dict) -> str:
    """The chip check reads the last line of stdout and takes exactly these
    keys; everything else the run has to say goes on the lines before."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--toy", action="store_true",
                    help="CPU rehearsal of the control flow at a few thousand "
                         "rows (Pallas interpreter); needs JAX_PLATFORMS=cpu "
                         "set explicitly; never prints the success line")
    args = ap.parse_args(argv)
    toy = args.toy
    _check_engine_env(toy)
    if toy:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise RuntimeError("--toy is a CPU rehearsal and runs only with "
                               "JAX_PLATFORMS=cpu set explicitly")
        os.environ["MMLSPARK_TPU_PALLAS_INTERPRET"] = "1"
        print("REHEARSAL (--toy): control flow on the CPU through the "
              "Pallas interpreter; proves nothing about the chip")
    n, iters, n_predict = ((4096, 8, 2048) if toy
                           else (1_000_000, 10, 200_000))

    device = device_gate(toy)
    ndev = device["count"]
    report = Report(f"{device['platform']} {device['kind']} x{ndev}")
    with report.phase("kernel"):
        kernel_phase(n, toy)
    from mmlspark_tpu.utils.synthetic import higgs_like
    X, y = higgs_like(n)            # the signal bench.py trains on too
    with report.phase("train"):
        model = train_phase(X, y, iters, ndev, toy)
    with report.phase("predict"):
        predict_phase(model, X[:n_predict])
    with report.phase("serve"):
        serve_phase(model, X, device["platform"])
    with report.phase("several chips"):
        several_chips_phase(X, y, max(2, iters // 2), ndev)

    summary = {"device": device, "phases": report.phases, "claim": None}
    if toy:
        print("REHEARSAL passed; this is not the chip check's result: "
              + json.dumps(summary))
        return 0
    print("summary (set-up facts, not rates): " + json.dumps(summary))
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
