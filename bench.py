"""Headline benchmark: distributed-GBDT training throughput (trees/sec).

Matches BASELINE.json's primary metric ("LightGBM trees/sec"): trains a
LightGBM-parity booster on a Higgs-like dense table (1M rows x 28 features,
num_leaves=31, max_bin=255 — LightGBM's canonical benchmark shape) on the TPU
and reports trees/sec.

``vs_baseline`` anchors against 15 trees/sec — the ballpark of LightGBM 2.3 on
a single multicore CPU node at this shape (the reference's own headline is
"10-30% faster than SparkML GBT" with no absolute numbers —
/root/reference/docs/lightgbm.md:17-21 — so an absolute anchor is stated here
explicitly and kept fixed across rounds for comparability).

Measurement convention: the timed phase is train_booster against a
pre-constructed LightGBMDataset — the same convention as LightGBM's published
timings, which call train() on a pre-built lgb.Dataset (and as the anchor
number). One-time ingest cost (binner fit + host->device transfer + device
binning) is reported separately as ``ingest_sec``, and
``end_to_end_trees_per_sec`` gives the rate with ingest folded in.

One process: ``python bench.py`` is the process that holds the chip, for the
whole run, and it exits non-zero when jax finds no TPU. The one exception is
an explicit ``JAX_PLATFORMS=cpu``: a toy-size run of the same control flow,
every line stamped ``platform: cpu`` — never a device number. Every printed
line carries ``platform``, ``device_kind`` and ``device_count`` as jax reports
them. Partial lines are printed as the run goes; the last JSON line on stdout
is the run's record. A failure anywhere propagates (non-zero exit, traceback)
instead of becoming a ``-1`` in a line that exits 0.

``python bench.py cold_warm_start`` is a separate entry for the worker
cold-vs-warm start contrast: its parent never touches jax, because each of its
children (trainer, bundle builder, two workers) needs the chip in turn.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

BASELINE_TREES_PER_SEC = 15.0


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _dump_metrics_snapshot(leg: str, wall_start: float = 0.0) -> None:
    """Opt-in telemetry dump next to the BENCH_*.json line:
    ``GRAFT_BENCH_METRICS_SNAPSHOT=<path>`` writes the process-wide
    metrics registry (docs/observability.md) accumulated over the bench —
    per-stage span histograms, serving counters, device-memory gauges —
    as JSON under ``"metrics"``, plus leg health meta: wall-clock
    start/end/duration and per-site watchdog stall counts, so a round's
    throughput line self-reports whether the run was clean or wedged.
    The platform is spliced into the filename (``m.json`` ->
    ``m.tpu.json``), so a toy CPU run never overwrites a chip run's
    breakdown."""
    path = os.environ.get("GRAFT_BENCH_METRICS_SNAPSHOT")
    if not path:
        return
    root, ext = os.path.splitext(path)
    path = f"{root}.{leg}{ext or '.json'}"
    try:
        from mmlspark_tpu.io.serving import roofline_payload
        from mmlspark_tpu.observability import metrics as _obs_metrics
        from mmlspark_tpu.observability import watchdog as _obs_watchdog
        wall_end = time.time()
        payload = {
            "leg": leg,
            "wall_clock": {"start": wall_start, "end": wall_end,
                           "seconds": round(wall_end - wall_start, 3)
                           if wall_start else None},
            "watchdog_stalls": _obs_watchdog.stall_counts(),
            # the measured roofline/HBM ledgers ride beside the metrics so
            # tools/roofline_report.py can re-render a dumped leg offline
            "roofline": roofline_payload(),
            "metrics": _obs_metrics.get_registry().snapshot(),
        }
        # SLO verdicts + sampled tail timelines (tools/tail_report.py
        # re-renders the attribution offline); both empty when no
        # objective was configured for the bench run
        from mmlspark_tpu.observability import slo as _obs_slo
        from mmlspark_tpu.observability import tailsampler as _obs_tail
        payload["slo"] = _obs_slo.snapshot_payload()
        payload["tail"] = _obs_tail.snapshot_payload()
        # auto-tuner provenance: which knobs were measured-resolved (and
        # from where — measured vs store vs pinned) during this leg,
        # so an A/B round is attributable to tuning rather than noise
        from mmlspark_tpu import tuning as _tuning
        payload["tuning"] = _tuning.provenance()
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
    except Exception as e:  # noqa: BLE001 — telemetry must not fail a bench
        print(f"metrics snapshot failed: {e!r}", file=sys.stderr)


def _measured_roofline_keys() -> dict:
    """``*_roofline_pct`` keys for the bench line, from the MEASURED
    ledger (cost_analysis x observed wall time), not the analytic model
    in ``_gbdt_roofline``. Per executable kind, the hotter of the FLOP /
    byte percentages; absent entirely when peaks are unknown (a CPU) —
    the ``_pct`` suffix keeps every one of these report-only in
    tools/bench_regression.py, which gates rates alone."""
    out: dict = {}
    try:
        from mmlspark_tpu.observability import roofline as _obs_roofline
        best: dict = {}
        for e in _obs_roofline.snapshot_payload().get("executables", []):
            pcts = [p for p in (e.get("flops_pct"), e.get("bytes_pct"))
                    if p is not None]
            if not pcts:
                continue
            kind = str(e.get("kind") or "unknown")
            best[kind] = max(best.get(kind, 0.0), max(pcts))
        for kind, pct in sorted(best.items()):
            out[f"gbdt_{kind}_roofline_pct"] = round(pct, 3)
    except Exception as e:  # noqa: BLE001 — telemetry must not fail a bench
        print(f"measured roofline keys failed: {e!r}", file=sys.stderr)
    return out


def _roofline_epilogue(leg: str) -> None:
    """Bench epilogue: hot executables as %-of-roofline plus the serving
    leg as a stage-time table, rendered by tools/roofline_report.py.
    Printed to stderr — stdout carries only the JSON line contract."""
    try:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools", "roofline_report.py")
        spec = importlib.util.spec_from_file_location("_roofline_report",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from mmlspark_tpu.io.serving import roofline_payload
        from mmlspark_tpu.observability import metrics as _obs_metrics
        text = mod.render_text(roofline_payload(),
                               _obs_metrics.get_registry().snapshot())
        print(f"-- roofline epilogue ({leg} leg) --\n{text}",
              file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001 — telemetry must not fail a bench
        print(f"roofline epilogue failed: {e!r}", file=sys.stderr)


def _dump_flight_snapshot(leg: str) -> None:
    """``GRAFT_BENCH_FLIGHT_SNAPSHOT=<path>`` writes the flight
    recorder's event ring (docs/observability.md) next to the metrics
    snapshot — span tails, compile events (cache key / wall time / XLA
    cost), failovers — so a slow round ships its event sequence, not just
    its aggregates. Same platform splice as the metrics dump."""
    path = os.environ.get("GRAFT_BENCH_FLIGHT_SNAPSHOT")
    if not path:
        return
    root, ext = os.path.splitext(path)
    path = f"{root}.{leg}{ext or '.json'}"
    try:
        from mmlspark_tpu.observability import flight as _obs_flight
        _obs_flight.dump(path)
    except Exception as e:  # noqa: BLE001 — telemetry must not fail a bench
        print(f"flight snapshot failed: {e!r}", file=sys.stderr)


def main() -> None:
    if sys.argv[1:] == ["cold_warm_start"]:
        _emit(_cold_warm_start())
        return
    if sys.argv[1:]:
        raise SystemExit(f"usage: {sys.argv[0]} [cold_warm_start]")
    from mmlspark_tpu.utils import compile_cache

    compile_cache.ensure()

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"bench.py needs a TPU, jax found {platform!r}; there is no "
            "fallback (a toy CPU run of the control flow needs an explicit "
            "JAX_PLATFORMS=cpu)")
    _run_leg(on_tpu=(platform == "tpu"))


def _sharded_gbdt_rates(X, y, max_bin: int, iters: int) -> dict:
    """Sharded-training scaling leg, in this process: the same depthwise
    config and global rows on ``make_mesh(devices=jax.devices()[:k])`` for
    k = 1 and k = all devices. On a CPU the "devices" are virtual
    (xla_force_host_platform_device_count) and timeshare the host cores, so
    the ratio prices the SPMD overhead of the sharded round loop and says
    nothing about parallel hardware."""
    import jax

    from mmlspark_tpu.models.gbdt.booster import (LightGBMDataset,
                                                  train_booster)
    from mmlspark_tpu.models.gbdt.growth import GrowConfig
    from mmlspark_tpu.parallel.mesh import make_mesh

    ndev = len(jax.devices())
    if ndev < 2:
        return {"sharded_note": "one device attached: the sharded scaling "
                                "leg needs >= 2, skipped"}
    kw = dict(num_iterations=iters, objective="binary",
              cfg=GrowConfig(num_leaves=31, min_data_in_leaf=20,
                             growth_policy="depthwise"))
    out = {}
    for k in (1, ndev):
        ds = LightGBMDataset.construct(
            X, y, max_bin=max_bin, bin_sample_count=min(len(y), 200_000),
            mesh=make_mesh(devices=jax.devices()[:k]))
        train_booster(dataset=ds, **kw)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            train_booster(dataset=ds, **kw)
            best = min(best, time.perf_counter() - t0)
        out[f"gbdt_sharded_trees_per_sec_{k}dev"] = round(iters / best, 3)
    out["sharded_scaling_x"] = round(
        out[f"gbdt_sharded_trees_per_sec_{ndev}dev"]
        / out["gbdt_sharded_trees_per_sec_1dev"], 3)
    return out


def _run_leg(on_tpu: bool) -> None:
    leg_wall_start = time.time()
    import jax
    import numpy as np

    from mmlspark_tpu.models.gbdt.booster import (LightGBMDataset,
                                                  train_booster)
    from mmlspark_tpu.models.gbdt.growth import GrowConfig
    from mmlspark_tpu.utils.synthetic import higgs_like

    if on_tpu:
        n_rows, n_feat, max_bin, bench_iters = 1_000_000, 28, 255, 40
    else:  # toy CPU run of the control flow: keep it tractable
        n_rows, n_feat, max_bin, bench_iters = 50_000, 28, 63, 8
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "device_kind": d0.device_kind,
              "device_count": len(jax.devices())}

    X, y = higgs_like(n_rows)      # the signal chip_smoke.py's floor is set on

    # depthwise growth: one batched histogram pass per level instead of one
    # per split batch (leafwise best-first remains the API default for
    # strict LightGBM parity, and is a secondary below)
    cfg = GrowConfig(num_leaves=31, min_data_in_leaf=20,
                     growth_policy="depthwise")
    common = dict(objective="binary", cfg=cfg)

    # Dataset construction (binner fit + transfer + device binning) happens
    # once, exactly like LightGBM's own measurement convention: its published
    # timings run train() against a pre-constructed lgb.Dataset, and the
    # 15 trees/sec anchor is a train-phase number. Ingest cost is reported
    # separately below (ingest_sec / end_to_end_trees_per_sec).
    t0 = time.perf_counter()
    ds = LightGBMDataset.construct(X, y, max_bin=max_bin,
                                   bin_sample_count=200_000)
    ingest_s = time.perf_counter() - t0

    # warmup: the fused multi-iteration executable is specialized on the
    # iteration count, so warm with the exact benched config — the timed runs
    # then measure pure training throughput. Best of two timed runs
    # (repeats and spreads per cell are the benchmark rebuild's, ROADMAP S1).
    train_booster(dataset=ds, num_iterations=bench_iters, **common)

    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        booster = train_booster(dataset=ds, num_iterations=bench_iters,
                                **common)
        dt = min(dt, time.perf_counter() - t0)
    trees_per_sec = bench_iters / dt

    # ONE primary dict feeds both the immediate partial line and the full
    # line below — the two must never diverge on metric name or anchor.
    primary = {
        "metric": ("gbdt_trees_per_sec_1M_rows_28f" if on_tpu else
                   "gbdt_trees_per_sec_50k_rows_28f"),
        "value": round(trees_per_sec, 3), "unit": "trees/sec",
        "vs_baseline": round(trees_per_sec / BASELINE_TREES_PER_SEC, 3),
        **device,
    }
    def _partial(note: str, **extra) -> None:
        # snapshot lines share the primary dict and the last-line-wins
        # convention; the full line at the end supersedes them all
        print(json.dumps(dict(primary, **extra, partial=note)), flush=True)

    # Publish the primary-only line IMMEDIATELY: if this leg is killed
    # while a secondary compiles (cold cache on a slow box — the shape of
    # two lost rounds), the real headline number still stands.
    _partial("primary only; superseded by the full line when all "
             "secondaries finish")

    # secondary GBDT configs (fewer iterations: they share the warm compile
    # cache and only need a rate, not a long soak):
    # - leafwise: the strict LightGBM-parity default users get
    # - max_bin=63: the accelerator-throughput config (LightGBM's own GPU
    #   docs recommend 63 bins; the Pallas kernel packs 2 features per
    #   128-lane dot at that width)
    sec_iters = max(8, bench_iters // 4)
    ds63 = LightGBMDataset.construct(X, y, max_bin=63,
                                     bin_sample_count=200_000)

    def _rate(dset, **over):
        kw = dict(common)
        kw.update({k: v for k, v in over.items() if k != "cfg_over"})
        if "cfg_over" in over:
            kw["cfg"] = cfg._replace(**over["cfg_over"])
        train_booster(dataset=dset, num_iterations=sec_iters, **kw)
        best = float("inf")
        for _ in range(2):
            t = time.perf_counter()
            train_booster(dataset=dset, num_iterations=sec_iters, **kw)
            best = min(best, time.perf_counter() - t)
        return round(sec_iters / best, 3)

    leafwise_tps = _rate(ds, cfg_over=dict(growth_policy="leafwise"))
    # leafwise with int8 quantized grads
    leafwise_best_tps = _rate(ds, cfg_over=dict(
        growth_policy="leafwise", quantized_grad=True))
    leafwise_best63_tps = _rate(ds63, cfg_over=dict(
        growth_policy="leafwise", quantized_grad=True))
    # second snapshot: the leafwise-vs-depthwise story is the round's
    # acceptance criterion — publish it the moment it exists so a timeout
    # in the remaining secondaries cannot lose it
    _partial("primary + leafwise; superseded by the full line",
             leafwise_trees_per_sec=leafwise_tps,
             leafwise_best_trees_per_sec=leafwise_best_tps,
             leafwise_best63_trees_per_sec=leafwise_best63_tps)
    maxbin63_tps = _rate(ds63)
    # int8 quantized-gradient histograms (2x-rate MXU path) at both widths
    quant_tps = _rate(ds, cfg_over=dict(quantized_grad=True))
    quant63_tps = _rate(ds63, cfg_over=dict(quantized_grad=True))
    _partial("primary + leafwise + quantized; superseded by the full line",
             leafwise_trees_per_sec=leafwise_tps,
             leafwise_best_trees_per_sec=leafwise_best_tps,
             leafwise_best63_trees_per_sec=leafwise_best63_tps,
             maxbin63_trees_per_sec=maxbin63_tps,
             quantized_trees_per_sec=quant_tps,
             quantized_maxbin63_trees_per_sec=quant63_tps)

    # sharded scaling leg (1 vs all devices, same depthwise config)
    sharded = _sharded_gbdt_rates(X, y, max_bin, sec_iters)

    # scoring throughput: batched device tree traversal vs the reference's
    # row-wise JNI predict (LGBM_BoosterPredictForMatSingle,
    # LightGBMBooster.scala:250). predict() ends in the host download of
    # the scores — a real sync.
    n_score = min(n_rows, 200_000)

    def _predict_rate():
        booster.predict(X[:n_score])                   # compile
        sdt = float("inf")
        pred = None
        for _ in range(2):
            t0 = time.perf_counter()
            pred = booster.predict(X[:n_score])
            sdt = min(sdt, time.perf_counter() - t0)
        return round(n_score / sdt, 1), pred

    predict_rows_per_sec, pred = _predict_rate()

    def _predict_rate_lane(pdt):
        # quantized predict lane (int8 bin-id routing + quantized leaves,
        # resolved through quantize.resolve_predict_dtype): same shape and
        # warm-compile best-of-2 protocol as _predict_rate, so the ratio
        # key below is apples-to-apples. On a CPU the ratio mostly
        # reflects the cheaper host-side staging (uint8 quantize vs f32
        # copy) and says nothing about the chip.
        booster.predict(X[:n_score], predict_dtype=pdt)    # compile
        sdt = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            booster.predict(X[:n_score], predict_dtype=pdt)
            sdt = min(sdt, time.perf_counter() - t0)
        return round(n_score / sdt, 1)

    predict_int8_rows_per_sec = _predict_rate_lane("int8")
    quantized_predict_vs_f32_x = round(
        predict_int8_rows_per_sec / predict_rows_per_sec, 2)

    def _predict_streamed_rate():
        # streamed scoring with the double-buffered prefetch ON
        # (io/prefetch.py reads chunk i+1 while the device scores chunk
        # i): the delta vs gbdt_predict_rows_per_sec on the same shape is
        # the host-I/O overlap win, visible per round in the JSON line
        from mmlspark_tpu.models.gbdt.ingest import write_shards
        with tempfile.TemporaryDirectory() as d:
            xdir = os.path.join(d, "xshards")
            write_shards(list(np.array_split(X[:n_score], 4)), xdir)
            booster.predict_streamed(xdir, chunk_rows=65_536)  # compile
            sdt = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                booster.predict_streamed(xdir, chunk_rows=65_536)
                sdt = min(sdt, time.perf_counter() - t0)
        return round(n_score / sdt, 1)

    predict_streamed_rows_per_sec = _predict_streamed_rate()
    # sanity: the model must actually learn this signal (reuses the timed
    # prediction — no extra forest evaluation or re-compile)
    n_acc = min(len(pred), 100_000)
    acc = ((pred[:n_acc] > 0.5) == y[:n_acc]).mean()
    out = {
        **primary,                 # same metric/value/anchor/platform as
                                   # the partial line this supersedes
        "train_accuracy": round(float(acc), 4),
        "bench_iterations": bench_iters,
        "growth_policy": "depthwise",
        "measures": "train phase on pre-constructed LightGBMDataset "
                    "(lgb.Dataset convention); ingest reported separately",
        # round-over-round note: value/vs_baseline use this train-phase
        # convention since round 2; earlier rounds timed end-to-end fits, so
        # compare end_to_end_trees_per_sec against pre-r2 history.
        "cross_round_comparable": "end_to_end_trees_per_sec",
        "ingest_sec": round(ingest_s, 3),
        "end_to_end_trees_per_sec": round(bench_iters / (dt + ingest_s), 3),
        "gbdt_predict_rows_per_sec": predict_rows_per_sec,
        "gbdt_predict_rows_per_sec_int8": predict_int8_rows_per_sec,
        "quantized_predict_vs_f32_x": quantized_predict_vs_f32_x,
        "gbdt_predict_streamed_rows_per_sec": predict_streamed_rows_per_sec,
        "leafwise_trees_per_sec": leafwise_tps,
        "leafwise_best_trees_per_sec": leafwise_best_tps,
        "leafwise_best63_trees_per_sec": leafwise_best63_tps,
        "maxbin63_trees_per_sec": maxbin63_tps,
        "quantized_trees_per_sec": quant_tps,
        "quantized_maxbin63_trees_per_sec": quant63_tps,
        **sharded,
        # serving latency vs the reference's ~1 ms continuous-mode claim
        # (docs/mmlspark-serving.md:10-11). Host-only loop: no device in the
        # transform path.
        **_serving_latency(),
    }
    # roofline estimate: judge "fast" against the chip's peak, not only the
    # 15/s anchor (assumptions documented in the helper)
    peak_flops = _peak_flops() if on_tpu else None
    if peak_flops:
        out.update(_gbdt_roofline(n_rows, n_feat, max_bin, trees_per_sec,
                                  peak_flops))
    _partial("through predict/serving/roofline; superseded by the full line",
             **{k: v for k, v in out.items() if k not in primary})
    imgs_per_sec = _resnet50_imgs_per_sec(on_tpu)
    if on_tpu:
        # BASELINE.json config 3: ResNet-50 featurizer throughput; no
        # absolute reference anchor is published, so raw rate + MFU only
        out["resnet50_imgs_per_sec_chip"] = imgs_per_sec
        if peak_flops:
            # 3.86e9 MACs/img (He et al. 2015) x2 to match FMA-counted peak
            out["resnet50_mfu_est"] = round(
                imgs_per_sec * 2 * 3.86e9 / peak_flops, 4)
    else:
        # the toy run substitutes a toy CNN (width 8, 64x64) as a smoke
        # signal only — never reported under an accelerator-keyed name
        out["toy_cnn_smoke_imgs_per_sec"] = imgs_per_sec
    _partial("through resnet; superseded by the full line",
             **{k: v for k, v in out.items() if k not in primary})

    # BASELINE.json configs 4 + 5: VW hashed-SGD and ImageLIME throughput.
    # The reference publishes no absolute anchors for either ("parity"
    # targets) — raw rates are reported.
    out["vw_sgd_examples_per_sec"] = _vw_examples_per_sec(on_tpu)
    lime_rates = _imagelime_rows_per_sec(on_tpu)
    out["imagelime_rows_per_sec"] = lime_rates["rows_per_sec"]
    out["imagelime_perturbations_per_sec"] = \
        lime_rates["perturbations_per_sec"]
    out.update(_measured_roofline_keys())

    # auto-tuner provenance on the round line itself (None when no store
    # is configured): tools/bench_regression.py annotates — never gates —
    # provenance flips, so a moved number is attributable to "the tuner
    # flipped a knob" before it's read as "the code got slower"
    from mmlspark_tpu import tuning as _tuning
    out["tuning"] = _tuning.provenance()
    print(json.dumps(out))
    _dump_metrics_snapshot("tpu" if on_tpu else "cpu", leg_wall_start)
    _dump_flight_snapshot("tpu" if on_tpu else "cpu")
    _roofline_epilogue("tpu" if on_tpu else "cpu")


def _peak_flops():
    """Peak FLOP/s of this chip from the one peaks table
    (observability/roofline.py), or None: a device that is not in the table
    (or whose peak was typed into an env override) gets no roofline key at
    all, never an assumed 197 TFLOP/s."""
    from mmlspark_tpu.observability.roofline import resolve_peaks

    peaks = resolve_peaks()
    if not peaks["source"].startswith("table:"):
        return None
    return peaks["flops_per_second"]


def _gbdt_roofline(n_rows: int, n_feat: int, max_bin: int,
                   trees_per_sec: float, peak_flops: float) -> dict:
    """MXU streaming-time roofline for the one-hot histogram formulation.

    Model: each feature's [RB, BP] one-hot streams through ceil(BP/128)
    MXU tile-columns at 128x128 MACs/cycle regardless of the stat-axis
    occupancy (the systolic array cannot skip padding lanes), so the
    minimum per-pass time is cols/mxu_cols_per_sec with
    cols = n_rows * (n_feat / pack) * ceil(BP/128) and
    mxu_cols_per_sec = peak_flops / (2 * 128 * 128). A depthwise tree at
    num_leaves=31 takes ~6 level passes. This is the bf16 path; the int8
    quantized path streams 2x. An analytic estimate only — a sanity ratio
    for "is the program in the right decade", not a hard ceiling (ROADMAP
    S2 replaces it with a trace reduction).
    """
    import math

    if max_bin <= 64:
        bp = 1 << max(int(max_bin - 1).bit_length(), 3)
        pack = 128 // bp
        tile_cols = 1
    else:
        bp = -(-max_bin // 128) * 128
        pack = 1
        tile_cols = bp // 128
    cols_per_pass = n_rows * (n_feat / pack) * tile_cols
    mxu_cols_per_sec = peak_flops / (2 * 128 * 128)
    # depthwise levels actually executed before the 31-leaf budget is spent:
    # W = 1,2,4,8,16 -> ceil(log2(L)) passes (the W=16 level splits the last
    # 15 nodes; the slack levels are skipped at runtime)
    passes_per_tree = math.ceil(math.log2(31))
    roofline_tps = mxu_cols_per_sec / (cols_per_pass * passes_per_tree)
    return {"gbdt_roofline_tps_est": round(roofline_tps, 2),
            "gbdt_roofline_frac": round(trees_per_sec / roofline_tps, 3),
            "gbdt_roofline_assumes": "bf16 one-hot streaming, "
                                     f"{passes_per_tree} passes/tree, "
                                     f"peak {peak_flops / 1e12:g} TFLOP/s"}


# trains the cold/warm-start model in a child: the parent of the workers
# below must never touch jax (a process that has holds the chip, and every
# child here needs it in turn)
_TRAIN_SRC = """
import sys
import numpy as np
from mmlspark_tpu.models.gbdt.booster import train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig
rng = np.random.default_rng(0)
# a forest deep/wide enough that the fused predict executable's XLA compile
# is a real cost (the quantity a fleet rollout pays per worker per bucket) —
# a toy model would measure only interpreter+jax import, which both paths
# pay identically
X = rng.normal(size=(4000, 16)).astype(np.float32)
y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
booster = train_booster(X=X, y=y, num_iterations=30, objective="binary",
                        cfg=GrowConfig(num_leaves=63))
with open(sys.argv[1], "w") as f:
    f.write(booster.model_string())
"""


def _cold_warm_start() -> dict:
    """Fleet cold-start contrast (``python bench.py cold_warm_start``):
    seconds from worker process spawn to its first successful /predict,
    cold (JIT compiles on the worker) vs warm (prewarmed from an AOT
    serving bundle, ``mmlspark_tpu/bundles``). The scenario is a fleet
    machine where nothing is mounted but the model and (warm case) the
    bundle: the cold worker gets an empty compile cache, the warm one the
    bundle's own shipped xla_cache. Includes interpreter + jax import, which
    is the honest number a rolling restart pays. One child at a time holds
    the chip; this process stays off jax."""
    import re
    import signal
    import urllib.request

    if "jax" in sys.modules:
        raise RuntimeError("the cold/warm parent must stay off jax")
    env = dict(os.environ)
    # no cache placed from outside: bundle build fills (and the warm worker
    # reads) the bundle's xla_cache
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # the COLD worker must be genuinely cold: an ambient bundle knob
    # would run the prewarm path and contaminate the contrast
    env.pop("MMLSPARK_TPU_BUNDLE_DIR", None)
    with tempfile.TemporaryDirectory() as d:
        model = os.path.join(d, "model.txt")
        subprocess.run([sys.executable, "-c", _TRAIN_SRC, model], env=env,
                       check=True, timeout=900)
        bundle = os.path.join(d, "model.bundle")
        t0 = time.perf_counter()
        subprocess.run([sys.executable, "-m", "mmlspark_tpu.bundles",
                        "build", "--model", model, "--out", bundle,
                        "--max-batch", "32"],
                       env=env, check=True, timeout=600,
                       stdout=subprocess.DEVNULL,
                       stderr=subprocess.DEVNULL)
        build_s = time.perf_counter() - t0

        def start_worker(extra, worker_env):
            t0 = time.monotonic()
            p = subprocess.Popen(
                [sys.executable, "-m", "mmlspark_tpu.io.serving_main",
                 "worker", "--model", model, "--registry",
                 os.path.join(d, "reg"), "--host", "localhost",
                 "--port", "0", "--max-batch", "32"] + extra,
                env=worker_env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True)
            try:
                m = re.search(r"serving on \S+:(\d+)",
                              p.stdout.readline() or "")
                if not m:
                    raise RuntimeError("worker printed no ready-line")
                port = int(m.group(1))
                body = json.dumps({"features": [0.1] * 16}).encode()
                deadline = time.monotonic() + 120
                while True:
                    try:
                        req = urllib.request.Request(
                            f"http://localhost:{port}/serving",
                            data=body, method="POST")
                        with urllib.request.urlopen(req, timeout=5) as r:
                            if r.status == 200:
                                return time.monotonic() - t0
                    except OSError:
                        pass
                    if time.monotonic() > deadline:
                        raise RuntimeError("no successful /predict in 120s")
                    time.sleep(0.02)
            finally:
                p.send_signal(signal.SIGTERM)
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()

        cold = start_worker([], dict(
            env, JAX_COMPILATION_CACHE_DIR=os.path.join(d, "cold_cache")))
        warm = start_worker(["--bundle", bundle], env)
    return {"cold_start_seconds": round(cold, 3),
            "warm_start_seconds": round(warm, 3),
            "bundle_build_seconds": round(build_s, 3),
            "cold_vs_warm_start_x": round(cold / max(warm, 1e-9), 2)}


def _serving_latency() -> dict:
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tests.test_serving_latency import (serving_latency_stats,
                                            serving_model_latency_stats)
    # continuous-batching A/B: both engines measured in one round,
    # interleaved t/a/t/a so box jitter hits both. The threaded keys keep
    # their historical names (bench_regression gates them round-over-
    # round); the async engine's keys carry an _async suffix until the
    # engine becomes the default — suffixed names never collide with (or
    # false-flag against) the threaded history.
    runs = {"threaded": [], "async": []}
    for _ in range(2):
        for eng in ("threaded", "async"):
            runs[eng].append(serving_latency_stats(
                n_seq=200, n_conc=8, conc_each=50, engine=eng))
    best = {eng: max(rs, key=lambda r: r["concurrent_rps"])
            for eng, rs in runs.items()}
    s = best["threaded"]
    # SLO-compliance keys per serving leg: measured p99 against the
    # serving north-star objective (p99 < 25 ms — the p99-at-SLO
    # yardstick of the Gemma-on-TPU serving comparison). margin_x > 1
    # means the leg sits inside the objective, with that much headroom;
    # the _x/_ms suffixes keep these outside bench_regression's rate
    # gate (report-only), like every other secondary.
    slo_target_ms = 25.0
    out = {"serving_p50_ms": round(s["p50_ms"], 3),
           "serving_p99_ms": round(s["p99_ms"], 3),
           "serving_concurrent_rps": round(s["concurrent_rps"], 1),
           "serving_vs_1ms_claim": round(1.0 / max(s["p50_ms"], 1e-9), 2),
           "serving_slo_p99_target_ms": slo_target_ms,
           "serving_slo_margin_x": round(
               slo_target_ms / max(s["p99_ms"], 1e-9), 2)}
    a = best["async"]
    out["serving_p50_ms_async"] = round(a["p50_ms"], 3)
    out["serving_p99_ms_async"] = round(a["p99_ms"], 3)
    out["serving_concurrent_rps_async"] = round(a["concurrent_rps"], 1)
    out["serving_async_vs_threaded_x"] = round(
        a["concurrent_rps"] / max(s["concurrent_rps"], 1e-9), 2)
    out["serving_slo_margin_x_async"] = round(
        slo_target_ms / max(a["p99_ms"], 1e-9), 2)
    # model-in-loop: compiled GBDT scoring each micro-batch, the
    # host->device->host hop included
    m = serving_model_latency_stats()
    out["serving_model_in_loop_p50_ms"] = round(m["p50_ms"], 3)
    out["serving_model_in_loop_p99_ms"] = round(m["p99_ms"], 3)
    out["serving_model_in_loop_rps"] = round(m["concurrent_rps"], 1)
    # int8 admission on the async rows path: requests quantize into uint8
    # slots and score through the int8 predictor lane — the end-to-end
    # quantized serving number (serving_main's booster configuration)
    from tests.test_serving_latency import serving_async_model_latency_stats
    qi = serving_async_model_latency_stats(predict_dtype="int8")
    if qi.get("predict_dtype") == "int8":
        out["serving_concurrent_rps_async_int8"] = round(
            qi["concurrent_rps"], 1)
        out["serving_p50_ms_async_int8"] = round(qi["p50_ms"], 3)
    return out


def _resnet50_imgs_per_sec(on_tpu: bool) -> float:
    """ImageFeaturizer throughput on ResNet-50 (bottleneck, bf16 activations),
    224x224 inputs, pool-layer capture — the transfer-learning workload of
    the reference's notebook example 9 (CNTKModel ResNet-50 featurizer).

    On the toy CPU run a toy CNN runs instead purely as a smoke signal; the
    caller reports it under a toy-named key, never as a chip number.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.dnn.cnn import (CNNConfig, apply_cnn,
                                             init_cnn_params)

    if on_tpu:
        cfg = CNNConfig(num_classes=1000, stage_sizes=(3, 4, 6, 3), width=64,
                        block="bottleneck", input_hw=(224, 224),
                        dtype=jnp.bfloat16)
        batch, reps = 128, 8
    else:
        cfg = CNNConfig(num_classes=10, stage_sizes=(1, 1, 1, 1), width=8,
                        block="bottleneck", input_hw=(64, 64))
        batch, reps = 8, 2
    params = init_cnn_params(cfg, jax.random.PRNGKey(0))

    @jax.jit
    def featurize(p, x):
        _, acts = apply_cnn(p, x, cfg, capture=["pool"])
        return acts["pool"]

    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, *cfg.input_hw, 3)).astype(np.float32))
    jax.block_until_ready(featurize(params, x))    # compile + materialize
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = featurize(params, x)
    # dispatches execute in program order: the last output waits for all
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return round(batch * reps / dt, 1)


def _vw_examples_per_sec(on_tpu: bool) -> float:
    """VW-parity hashed-SGD training throughput on sparse text-like data —
    BASELINE.json config 4 (VowpalWabbitClassifier sparse text, native SGD →
    XLA). Shape: nnz hashed tokens/example into a 2^18 weight table, one
    pass, adaptive (AdaGrad-scaled) updates. The timed call follows
    the repo convention: data is pre-padded/transferred (``_prep_sgd_data``),
    and ``train_sgd`` ends by downloading the weight vector — the natural
    sync point (it IS the trained model).
    """
    import numpy as np

    from mmlspark_tpu.models.vw.sgd import (SGDConfig, _prep_sgd_data,
                                            train_sgd)

    n, nnz = (400_000, 32) if on_tpu else (50_000, 16)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, 1 << 18, size=(n, nnz), dtype=np.int32)
    val = np.ones((n, nnz), np.float32)
    y = (idx[:, 0] & 1).astype(np.float32)
    cfg = SGDConfig(num_bits=18, loss="logistic", num_passes=1,
                    batch_size=512)
    from mmlspark_tpu.parallel import mesh as meshlib
    mesh = meshlib.get_default_mesh()
    prepped = _prep_sgd_data(idx, val, y, None, cfg, mesh)
    train_sgd(idx, val, y, None, cfg, mesh=mesh, prepped=prepped)  # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        train_sgd(idx, val, y, None, cfg, mesh=mesh, prepped=prepped)
        best = min(best, time.perf_counter() - t0)
    return round(n / best, 1)


def _imagelime_rows_per_sec(on_tpu: bool) -> dict:
    """ImageLIME explanation throughput with a device CNN in the scoring
    loop — BASELINE.json config 5 (ImageLIME over CNTKModel, perturbation
    batches on the accelerator). Each row costs ``nSamples`` masked
    forward passes (device) plus SLIC superpixels and a lasso fit (host);
    rows/sec measures that whole pipeline, perturbations/sec isolates the
    device-facing rate. The transform's own output materialization is the
    sync point (coefficients come back as numpy).
    """
    import numpy as np

    from mmlspark_tpu.core.dataset import Dataset
    from mmlspark_tpu.explain.lime import ImageLIME
    from mmlspark_tpu.models.dnn.cnn import (CNNConfig, apply_cnn,
                                             init_cnn_params)
    from mmlspark_tpu.models.dnn.scoring import DNNModel

    import jax

    hw, width, n_imgs, ns = ((64, 64), 16, 8, 200) if on_tpu else \
        ((32, 32), 4, 3, 50)
    cfg = CNNConfig(num_classes=2, stage_sizes=(1, 1), width=width,
                    input_hw=hw)
    params = init_cnn_params(cfg, jax.random.PRNGKey(0))
    apply_fn = lambda p, x, capture=("logits",): apply_cnn(p, x, cfg, capture)  # noqa: E731
    inner = (DNNModel(params, apply_fn)
             .set(inputCol="img", outputCol="score", outputNode="logits",
                  miniBatchSize=256))
    rng = np.random.default_rng(0)
    imgs = [rng.normal(size=(*hw, 3)).astype(np.float32)
            for _ in range(n_imgs)]
    lime = ImageLIME(model=inner).set(
        inputCol="img", outputCol="exp", predictionCol="score",
        nSamples=ns, cellSize=16.0)
    lime.transform(Dataset({"img": imgs[:1]}))        # compile
    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        lime.transform(Dataset({"img": imgs}))
        dt = min(dt, max(time.perf_counter() - t0, 1e-9))
    return {"rows_per_sec": round(n_imgs / dt, 2),
            "perturbations_per_sec": round(n_imgs * ns / dt, 1)}


if __name__ == "__main__":
    main()
