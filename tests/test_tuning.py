"""Auto-tuner decision layer (mmlspark_tpu/tuning): the PR 19
measure→decide loop's load-bearing contracts, each pinned here:

* decisions are a pure function of the recorded ledger — the same
  observation sequence replayed into two fresh store directories writes
  BYTE-IDENTICAL ``tuning.json`` files;
* the second process warm-starts: every decision read back from the
  store resolves with ``source=store`` and is not decided again;
* a fingerprint-skewed (or unreadable) store degrades LOUDLY to the
  static rules — flight event + ``tuning_store_degraded_total`` — and
  is never overwritten by the degraded process;
* dispatch pacing never holds a breaching endpoint: SLO fast-window
  burn > 1 bypasses the hold window immediately;
* slot auto-sizing reconciles the measured p99.9 against the HBM
  claim headroom and the pow2 batch cap;
* a tuned-ladder bundle prewarm serves a rung-shaped first predict
  with zero compile events (slow-marked: trains + AOT-lowers).
"""

import json
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu import tuning
from mmlspark_tpu.io.aserve.server import AsyncServingServer
from mmlspark_tpu.io.aserve.slots import resolve_slots
from mmlspark_tpu.observability import flight, metrics, slo
from mmlspark_tpu.tuning import decisions, store


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("MMLSPARK_TPU_TUNING_DIR", "MMLSPARK_TPU_TUNE_MIN_SAMPLES",
                "MMLSPARK_TPU_TUNE_HOLD_MS", "MMLSPARK_TPU_TUNE_HOLD_CAP_MS",
                "MMLSPARK_TPU_ASERVE_SLOTS", "MMLSPARK_TPU_SLO"):
        monkeypatch.delenv(var, raising=False)
    prev = metrics.set_enabled(True)
    metrics.reset()
    flight.clear()
    tuning.reset()
    slo.reset()
    yield
    tuning.reset()
    slo.reset()
    flight.clear()
    metrics.reset()
    metrics.set_enabled(prev)


#: the ladder ``_drive_full_ledger``'s batch sizes decide
_LADDER = (1, 2, 4, 8, 40, 104)


def _tuning_events(**match):
    return [e for e in flight.events()
            if e.get("kind") == "tuning"
            and all(e.get(k) == v for k, v in match.items())]


def _drive_full_ledger(store_dir):
    """Replay ONE fixed observation sequence through the public API —
    the byte-determinism and warm-start tests both key off this exact
    ledger (callers pin MMLSPARK_TPU_TUNE_MIN_SAMPLES=16)."""
    tuning.reset()
    tuning.configure(store_dir=str(store_dir))
    assert tuning.enabled()
    tuning.note_slot_geometry(row_bytes=24, max_batch=512)
    tuning.observe_score(0.004)
    tuning.observe_score(0.0044)
    tuning.observe_forming_wait(0.0002)
    for n in (3, 5, 37, 37, 100) * 8:
        tuning.observe_batch_size(n)
    tuning.flush()
    return tuning.resolve_bucket_ladder()


class TestDecisionFunctions:
    """The pure layer: ledger evidence in, knob values out — no jax, no
    clock, no environment."""

    def test_bucket_ladder_rungs(self):
        counts = {"3": 8, "5": 8, "37": 16, "100": 8}
        # p50=37→40, p90/p99/max=100→104, pow2 head below the rungs
        assert decisions.decide_bucket_ladder(counts, 16) == \
            (1, 2, 4, 8, 40, 104)

    def test_bucket_ladder_below_bar_or_pow2_declines(self):
        assert decisions.decide_bucket_ladder({"37": 3}, 16) is None
        assert decisions.decide_bucket_ladder({}, 1) is None
        # a workload pow2 already fits: re-keying every program wins
        # nothing, so no decision
        assert decisions.decide_bucket_ladder({"64": 100}, 16) is None

    def test_ladder_pad(self):
        ladder = (1, 2, 4, 8, 40, 104)
        assert decisions.ladder_pad(3, ladder) == 4
        assert decisions.ladder_pad(37, ladder) == 40
        assert decisions.ladder_pad(40, ladder) == 40
        # out-of-distribution batches keep the static pow2 behavior
        assert decisions.ladder_pad(105, ladder) == 128

    def test_percentile_nearest_rank(self):
        counts = {"1": 50, "10": 49, "1000": 1}
        assert decisions.percentile_from_counts(counts, 0.50) == 1
        assert decisions.percentile_from_counts(counts, 0.99) == 10
        assert decisions.percentile_from_counts(counts, 1.0) == 1000
        assert decisions.percentile_from_counts({}, 0.5) == 0

    def test_slots_headroom_halving(self):
        counts = {"900": 100}
        # p99.9 = 900 → pow2 1024, no geometry → no reconcile
        assert decisions.decide_slots(counts, 2048, 10) == 1024
        # clamped to the pow2 batch cap
        assert decisions.decide_slots(counts, 512, 10) == 512
        # ping-pong = 2 buffers of slots*row_bytes must fit the headroom:
        # 2*1024*1024B > 1MiB → halve once to 512 (2*512*1024B == 1MiB fits)
        assert decisions.decide_slots(counts, 2048, 10, row_bytes=1024,
                                      headroom_bytes=float(1 << 20)) == 512
        # headroom can never drive the table below one slot
        assert decisions.decide_slots(counts, 2048, 10, row_bytes=1 << 30,
                                      headroom_bytes=1.0) == 1
        # below the evidence bar: no decision
        assert decisions.decide_slots(counts, 2048, 200) is None

    def test_hold_window_gates(self):
        # memory-bound + under-occupied + fast forming → hold ≈ 2×score
        assert decisions.decide_hold_window(
            "memory", 0.0001, 0.0008, 3.0, 32, 0.002) == \
            pytest.approx(0.0016)
        # capped
        assert decisions.decide_hold_window(
            "memory", 0.0001, 0.0100, 3.0, 32, 0.002) == 0.002
        # compute-bound scales wall with rows: never hold
        assert decisions.decide_hold_window(
            "compute", 0.0001, 0.0008, 3.0, 32, 0.002) == 0.0
        # slot table already half full: nothing to gain
        assert decisions.decide_hold_window(
            "memory", 0.0001, 0.0008, 20.0, 32, 0.002) == 0.0
        # batches form as slowly as they score: the hold costs real wall
        assert decisions.decide_hold_window(
            "memory", 0.0005, 0.0008, 3.0, 32, 0.002) == 0.0


class TestDisabled:
    def test_disabled_without_store_dir(self):
        assert not tuning.enabled()
        assert tuning.resolve_bucket_ladder() is None
        assert tuning.resolve_hold_window() == 0.0
        assert tuning.resolve_slots_auto(64) is None
        assert tuning.provenance() is None
        assert tuning.snapshot_payload()["status"] == "disabled"


class TestStoreDeterminism:
    def test_same_ledger_same_store_bytes(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_TUNE_MIN_SAMPLES", "16")
        c1 = _drive_full_ledger(tmp_path / "a")
        c2 = _drive_full_ledger(tmp_path / "b")
        assert c1 == c2 == _LADDER
        b1 = (tmp_path / "a" / store.STORE_NAME).read_bytes()
        b2 = (tmp_path / "b" / store.STORE_NAME).read_bytes()
        assert b1 == b2
        payload = json.loads(b1)
        assert payload["format_version"] == store.FORMAT_VERSION
        dec = payload["decisions"]
        assert dec["bucket_ladder"]["choice"] == list(_LADDER)
        assert dec["bucket_ladder"]["source"] == "measured"
        assert dec["slots"]["choice"] == 128       # p99.9=100 → pow2
        assert "hold_window" in dec

    def test_second_process_warm_starts_from_store(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_TUNE_MIN_SAMPLES", "16")
        _drive_full_ledger(tmp_path)
        # "second process": fresh in-memory tuner, same store directory
        tuning.reset()
        flight.clear()
        tuning.configure(store_dir=str(tmp_path))
        # batches of another shape arrive: a decision read back is pinned,
        # the warm process must not decide again
        for _ in range(40):
            tuning.observe_batch_size(200)
        assert tuning.resolve_bucket_ladder() == _LADDER
        applied = _tuning_events(site="bucket_ladder")
        assert applied and applied[-1]["source"] == "store"
        assert tuning.resolve_slots_auto(512) == 128
        assert metrics.counter("tuning_decisions_total",
                               site="bucket_ladder",
                               choice=str(_LADDER)).value >= 1.0
        prov = tuning.provenance()
        assert prov["status"] == "ok"
        assert prov["bucket_ladder"] == list(_LADDER)

    def test_hold_env_pin_overrides_store(self, tmp_path, monkeypatch):
        tuning.configure(store_dir=str(tmp_path))
        monkeypatch.setenv("MMLSPARK_TPU_TUNE_HOLD_MS", "1.5")
        assert tuning.resolve_hold_window() == pytest.approx(0.0015)
        applied = _tuning_events(site="hold_window")
        assert applied and applied[-1]["source"] == "pinned"


class TestStoreDegrade:
    def test_fingerprint_skew_degrades_loudly_and_never_writes(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_TUNE_MIN_SAMPLES", "16")
        _drive_full_ledger(tmp_path)
        path = tmp_path / store.STORE_NAME
        payload = json.loads(path.read_text())
        payload["fingerprint"]["framework_version"] = "0.0.0-skewed"
        path.write_text(json.dumps(payload, indent=2, sort_keys=True)
                        + "\n")
        skewed_bytes = path.read_bytes()

        tuning.reset()
        flight.clear()
        metrics.reset()
        tuning.configure(store_dir=str(tmp_path))
        # every resolver answers static — no behavior change
        assert tuning.resolve_bucket_ladder() is None
        assert tuning.resolve_slots_auto(512) is None
        assert tuning.resolve_hold_window() == 0.0
        # ...but LOUDLY: flight event + status-labeled counter
        degraded = _tuning_events(event="store_degraded")
        assert degraded
        assert degraded[0]["status"] == "fingerprint_mismatch"
        assert any("framework_version" in m
                   for m in degraded[0]["mismatches"])
        assert metrics.counter("tuning_store_degraded_total",
                               status="fingerprint_mismatch").value == 1.0
        snap = tuning.snapshot_payload()
        assert snap["status"] == "degraded" and snap["mismatches"]
        assert tuning.provenance() == {"status": "degraded"}
        # a degraded process never persists over the skewed store — an
        # operator can still inspect exactly what mismatched
        for _ in range(40):
            tuning.observe_batch_size(37)
        tuning.flush()
        assert path.read_bytes() == skewed_bytes

    def test_unreadable_store_degrades(self, tmp_path):
        (tmp_path / store.STORE_NAME).write_text("{not json")
        tuning.configure(store_dir=str(tmp_path))
        assert tuning.resolve_bucket_ladder() is None
        degraded = _tuning_events(event="store_degraded")
        assert degraded and degraded[0]["status"] == "unreadable"


class TestHoldBurnBypass:
    """Dispatch pacing (site 2) against a live SLO plane: a breaching
    endpoint is NEVER held — its latency budget is already gone."""

    def _server(self):
        # constructible without start(): _hold_forming is pure
        # lock+event machinery over the forming buffer
        return AsyncServingServer(api_name="tuneapi")

    def test_burn_over_one_bypasses_hold(self):
        srv = self._server()
        srv._forming = [object()]
        srv._first_arrival = time.monotonic()
        slo.configure("tuneapi:p99<1ms")
        for _ in range(10):
            slo.observe_request("tuneapi", 0.050, 200)
        assert slo.current_burn("tuneapi") > 1.0
        t0 = time.monotonic()
        srv._hold_forming(0.5)
        assert time.monotonic() - t0 < 0.25
        assert metrics.counter("tuning_hold_outcomes_total",
                               api="tuneapi",
                               outcome="burn_bypass").value == 1.0
        assert metrics.counter("tuning_hold_outcomes_total",
                               api="tuneapi",
                               outcome="held").value == 0.0

    def test_healthy_endpoint_holds_full_window(self):
        srv = self._server()
        srv._forming = [object()]
        srv._first_arrival = time.monotonic()
        assert slo.current_burn("tuneapi") == 0.0   # no SLO configured
        t0 = time.monotonic()
        srv._hold_forming(0.05)
        elapsed = time.monotonic() - t0
        assert elapsed >= 0.04
        assert metrics.counter("tuning_hold_outcomes_total",
                               api="tuneapi",
                               outcome="held").value == 1.0

    def test_full_buffer_dispatches_immediately(self):
        srv = self._server()
        srv._forming = [object()] * srv.slots
        srv._first_arrival = time.monotonic()
        t0 = time.monotonic()
        srv._hold_forming(0.5)
        assert time.monotonic() - t0 < 0.25
        assert metrics.counter("tuning_hold_outcomes_total",
                               api="tuneapi",
                               outcome="held").value == 0.0

    def test_buffer_filling_mid_hold_cuts_the_wait(self):
        srv = self._server()
        srv._forming = [object()]
        srv._first_arrival = time.monotonic()

        def fill():
            with srv._lock:
                srv._forming = [object()] * srv.slots
            srv._wake.set()

        timer = threading.Timer(0.02, fill)
        timer.start()
        try:
            t0 = time.monotonic()
            srv._hold_forming(2.0)
            assert time.monotonic() - t0 < 1.0
        finally:
            timer.cancel()


class TestSlotsAutoEnv:
    def test_auto_without_decision_sizes_statically(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_ASERVE_SLOTS", "auto")
        # no store at all, and a store with no slots decision yet: both
        # fall back to the untuned rule (pow2 of the batch cap)
        assert resolve_slots(48) == 64
        tuning.configure(store_dir=str(tmp_path))
        assert resolve_slots(48) == 64

    def test_auto_resolves_measured_decision(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_TUNE_MIN_SAMPLES", "16")
        _drive_full_ledger(tmp_path)
        tuning.reset()
        tuning.configure(store_dir=str(tmp_path))
        monkeypatch.setenv("MMLSPARK_TPU_ASERVE_SLOTS", "auto")
        assert resolve_slots(512) == 128     # the measured p99.9, pow2
        assert resolve_slots(64) == 64       # clamped to the batch cap

    def test_explicit_count_still_wins(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_TUNE_MIN_SAMPLES", "16")
        _drive_full_ledger(tmp_path)
        tuning.reset()
        tuning.configure(store_dir=str(tmp_path))
        monkeypatch.setenv("MMLSPARK_TPU_ASERVE_SLOTS", "256")
        assert resolve_slots(512) == 256


@pytest.mark.slow
class TestTunedLadderBundle:
    """ISSUE 19 round-trip acceptance: a bundle built against a tuned
    store AOT-lowers the measured rungs, so a warmed worker's first
    rung-shaped predict compiles nothing."""

    def test_rung_shaped_first_predict_zero_compiles(self, tmp_path,
                                                     monkeypatch):
        from mmlspark_tpu.bundles import build_bundle, prewarm, \
            read_manifest
        from mmlspark_tpu.models.gbdt.booster import (
            Booster, _PREDICT_CACHE, predict_key_manifest, train_booster)
        from mmlspark_tpu.models.gbdt.growth import GrowConfig

        monkeypatch.setenv("MMLSPARK_TPU_TUNE_MIN_SAMPLES", "16")
        store_dir = tmp_path / "tuned"
        _drive_full_ledger(store_dir)
        tuning.reset()
        tuning.configure(store_dir=str(store_dir))
        assert tuning.resolve_bucket_ladder() == (1, 2, 4, 8, 40, 104)

        rng = np.random.default_rng(0)
        X = rng.normal(size=(400, 6)).astype(np.float32)
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
        booster = train_booster(X=X, y=y, num_iterations=3,
                                objective="binary",
                                cfg=GrowConfig(num_leaves=7,
                                               min_data_in_leaf=5))
        model = tmp_path / "model.txt"
        model.write_text(booster.model_string())
        bundle = tmp_path / "model.bundle"
        build_bundle(str(model), str(bundle), max_batch=40)

        b = Booster.from_string(model.read_text())
        # the 37-row plan pads to the tuned 40 rung, and that exact
        # executable is in the bundle
        man = read_manifest(bundle)
        want = {e["key_hash"] for e in predict_key_manifest(b, [37])}
        assert want and want <= {e["key_hash"] for e in man["entries"]}

        Xq = rng.normal(size=(37, 6)).astype(np.float32)
        _PREDICT_CACHE.clear()
        flight.clear()
        p_jit = b.predict(Xq)
        _PREDICT_CACHE.clear()
        flight.clear()
        stats = prewarm(str(model), str(bundle), boosters=[b])
        assert stats["status"] == "ok"
        flight.clear()
        p_warm = b.predict(Xq)
        compiles = [e for e in flight.events()
                    if e.get("kind") == "compile"]
        assert compiles == []
        assert np.array_equal(p_warm, p_jit)
