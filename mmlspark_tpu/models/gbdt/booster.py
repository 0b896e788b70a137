"""Boosting orchestration + the Booster (trained ensemble) container.

TPU-native equivalent of the reference's per-task training loop and booster
object (reference: lightgbm/TrainUtils.scala:220-315 ``trainCore`` — the
per-iteration loop with eval tracking, early stopping and delegate hooks;
lightgbm/LightGBMBooster.scala:186-339 — the inference/persistence side).

Design: the per-iteration work (gradients -> grow tree -> update scores ->
eval metrics) is ONE jitted shard_map program over the ``data`` mesh axis;
the Python host loop around it handles early stopping and callbacks, exactly
where the reference put its JVM-side loop. Trees come back as tiny fixed-shape
arrays per iteration and are stacked into the Booster.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
from jax.sharding import Mesh

from ...observability import flight as _flight
from ...observability import hbm as _hbm
from ...observability import metrics as _metrics
from ...observability import roofline as _roofline
from ...observability import spans as _spans
from ...observability import watchdog as _watchdog
from ...robustness.failpoints import fault_point as _failpoint
from ... import tuning as _tuning
from ...utils import compile_cache as _compile_cache
from ...ops.binning import QuantileBinner, bin_cols_device
from ...parallel import mesh as meshlib
from ...parallel import placement
from ...parallel.compat import shard_map
from ...parallel.placement import pspec as P
from . import quantize as _quantize
from .growth import (GrowConfig, Tree, bitset_words, grow_tree,
                     grow_tree_depthwise, hist_blocks_per_shard,
                     predict_forest_raw, predict_tree_binned,
                     run_tally_layout)
from .objectives import (GLOBAL_EVAL_METRICS, HIGHER_IS_BETTER, Objective,
                         eval_metric, get_objective, score_transform)


# bounded LRU of compiled boosting steps: one executable per
# (shape, config, mesh) combination; evict oldest so long-lived processes
# (sweeps, services) don't pin executables forever
_STEP_CACHE: "OrderedDict" = OrderedDict()
_STEP_CACHE_MAX = 32


def _cached_program(key, build):
    """Get-or-build a compiled program in the bounded LRU step cache."""
    prog = _STEP_CACHE.get(key)
    if prog is None:
        # wire the persistent compile cache before ANY cached program is
        # built — dataset construction (bin_cols, synth masks) builds
        # programs before train_booster's own ensure() runs
        _compile_cache.ensure()
        t0 = time.perf_counter()
        prog = build()
        # compile event: XLA hands this cache jitted programs that compile
        # lazily, so the recorded time is stage-out only — the predict
        # cache (below) is the one that observes real compile wall time
        _flight.record("program_build", cache="gbdt_step",
                       key=repr(key),
                       seconds=round(time.perf_counter() - t0, 6),
                       persistent_cache=_compile_cache.cache_dir() or "")
        _metrics.safe_counter("gbdt_program_builds_total",
                              cache="gbdt_step").inc()
        # roofline ledger entry: step programs compile lazily, so no
        # cost_analysis here — the entry still names the executable
        _roofline.register_executable(predict_key_hash(key), kind="step",
                                      label="gbdt_step")
        _STEP_CACHE[key] = prog
        while len(_STEP_CACHE) > _STEP_CACHE_MAX:
            _STEP_CACHE.popitem(last=False)
    else:
        _STEP_CACHE.move_to_end(key)
    return prog


def _hit_or_built(key) -> str:
    """Whether the step cache already holds ``key``'s program: the
    ``program`` attribute of a ``gbdt_fit`` span."""
    return "hit" if key in _STEP_CACHE else "built"


class _Phases(contextlib.ExitStack):
    """Back-to-back child spans of ``parent`` — the TPU analog of the
    reference's per-phase TrainingStats diagnostics
    (vw/VowpalWabbitBase.scala:27-46). ``enter(name)`` ends the phase that
    is open and starts the next, so the phases tile their parent without a
    ``with`` level each; leaving the stack ends the last one. Names are
    fixed: what varies goes into the parent's attributes."""

    def __init__(self, parent):
        super().__init__()
        self.parent = parent

    def enter(self, name: str):
        self.close()
        return self.enter_context(_spans.span(name))  # graftlint: disable=resource-leak (the stack is its own with-context at every use and closes the span on all paths)

    def program(self, path: str, key, build):
        """The ``gbdt_fit_program`` phase: get-or-build the fit's program
        and say on the parent which it was."""
        self.enter("gbdt_fit_program")
        self.parent.set(path=path, program=_hit_or_built(key))
        return _cached_program(key, build)


def _in_fit_span(train):
    """Run ``train`` inside a ``gbdt_fit`` span, its phases handed in as
    ``_phases`` (docs/observability.md, "Spans and device traces")."""
    @functools.wraps(train)
    def wrapped(*args, **kwargs):
        with _spans.span("gbdt_fit") as fit, _Phases(fit) as phases:
            return train(*args, _phases=phases, **kwargs)
    return wrapped


# --- single-buffer tree transfer -------------------------------------------
# A Tree has 13 leaf arrays; downloading them individually costs one
# device-to-host transfer each. pack_trees flattens everything into ONE
# int32 buffer on device (floats bitcast, bools widened) so the download is
# a single transfer; unpack_trees restores the exact arrays on host.

_TREE_FIELD_DTYPES = dict(
    feat=np.int32, thr_bin=np.int32, left=np.int32, right=np.int32,
    is_leaf=np.bool_, leaf_value=np.float32, node_count=np.int32,
    node_grad=np.float32, node_hess=np.float32, node_cnt=np.float32,
    split_gain=np.float32, node_value=np.float32, cat_bitset=np.uint32)


def pack_trees(trees: Tree, tally=None) -> jnp.ndarray:
    """Flatten a (possibly stacked) Tree into one int32 device buffer, a
    fit's run tallies (``_grow_with_warmup``) its tail: what the trees' growth
    ran reaches the host in the transfer that brings the trees
    (:func:`_unpack_fit`).

    The buffer is int32, not f32: small integers bitcast to f32 are
    subnormals, and the TPU flushes subnormals to zero somewhere in the
    f32 copy pipeline (observed: every int field read back as 0). Float
    bits ride bitcast inside int32 instead — integer ops never flush.
    """
    parts = []
    for arr in trees:
        if arr.dtype == jnp.bool_:
            arr = arr.astype(jnp.int32)
        if arr.dtype != jnp.int32:
            arr = lax.bitcast_convert_type(arr, jnp.int32)
        parts.append(arr.reshape(-1))
    if tally is not None:
        parts.append(tally.reshape(-1))
    return jnp.concatenate(parts)


def _tree_field_shape(name: str, lead: Tuple[int, ...], M: int,
                      BW: int) -> Tuple[int, ...]:
    """THE single source of truth for the packed-buffer field layout:
    per-tree ``[M, BW]`` for the category bitsets, scalar for node_count,
    ``[M]`` for every other field — shared by the host and device
    unpackers so the wire layout cannot drift between them."""
    return lead + ((M, BW) if name == "cat_bitset"
                   else () if name == "node_count" else (M,))


def unpack_trees(flat: np.ndarray, lead: Tuple[int, ...], M: int,
                 BW: int) -> Tree:
    """Inverse of :func:`pack_trees`: trees with leading dims ``lead``."""
    fields, off = {}, 0
    for name in Tree._fields:
        shape = _tree_field_shape(name, lead, M, BW)
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        seg = np.ascontiguousarray(flat[off:off + size])
        off += size
        dt = _TREE_FIELD_DTYPES[name]
        if dt == np.bool_:
            seg = seg.astype(np.bool_)
        elif dt != np.int32:
            seg = seg.view(dt)
        fields[name] = seg.reshape(shape)
    assert off == flat.size, (
        f"unpack_trees: buffer has {flat.size} elements, layout expects "
        f"{off} — num_leaves/num_bins mismatch between pack and unpack")
    return Tree(**fields)


def _grow_variants(cfg: GrowConfig) -> Tuple[GrowConfig, ...]:
    """The grow configurations a fit of ``cfg`` stages
    (:func:`_grow_with_warmup`): the full-precision one of the quantized
    warm-up first, where there is one."""
    if cfg.quantized_grad and cfg.quant_warmup_iters > 0:
        return cfg._replace(quantized_grad=False), cfg
    return (cfg,)


def _tally_words(cfg: GrowConfig) -> Tuple[int, ...]:
    """int32 words of each variant's run tally (``growth.run_tally_layout``);
    a tree's tally is the variants' end to end."""
    return tuple(1 + len(run_tally_layout(c)) for c in _grow_variants(cfg))


def _unpack_fit(flat: np.ndarray, lead: Tuple[int, ...], cfg: GrowConfig):
    """A fit's download (:func:`pack_trees` with its tail) as ``(trees,
    tallies)``: trees with leading dims ``lead`` and their run tallies
    ``[*lead, words]``."""
    tail = flat.size - int(np.prod(lead)) * sum(_tally_words(cfg))
    return (unpack_trees(flat[:tail], lead, 2 * cfg.num_leaves - 1,
                         bitset_words(cfg.num_bins)),
            flat[tail:].reshape(lead + (-1,)))


def _tell_run_tally(fit, cfg: GrowConfig, shards: int, K: int,
                    tallies) -> None:
    """What the fit ran, counted on the device where it ran
    (``growth.run_tally_layout``), told on its ``gbdt_fit`` span: ``passes``
    (histogram passes, the roots' included), ``launches`` (kernel launches a
    shard: a pass is one launch a block under ``hist_blocks``), ``slots``
    (node slots the passes ran at), ``live`` (positions of them that held
    rows) and ``iterations`` (boosting iterations run: an early stop says
    where). ``gbdt_hist_passes_run_total{width}`` moves by the runs, the
    roots' under ``width="root"``. ``tallies``: the downloads' ``[...,
    words]`` arrays (none where no iteration ran). With telemetry off the
    tail is dropped."""
    if not (_metrics.enabled() and tallies):
        return
    words = _tally_words(cfg)
    rows = np.concatenate([np.reshape(t, (-1, sum(words))) for t in tallies])
    total, at = rows.sum(axis=0), 0
    passes = slots = live = 0
    for c, n in zip(_grow_variants(cfg), words):
        widths = run_tally_layout(c)
        seg, at = total[at:at + n], at + n
        runs = seg[1:]
        live += int(seg[0])
        passes += int(runs.sum())
        slots += int(runs @ np.asarray(widths))
        for i, (width, run) in enumerate(zip(widths, runs)):
            if run:
                _metrics.safe_counter(
                    "gbdt_hist_passes_run_total",
                    width="root" if i == 0 else str(width)).inc(int(run))
    fit.set(passes=passes,
            launches=passes * max(1, hist_blocks_per_shard(cfg, shards)),
            slots=slots, live=live, iterations=len(rows) // K)


# --- device-resident inference hot path -------------------------------------
# The fused predictor: ONE compiled program evaluates the forest, sums the
# per-class tree outputs, adds the base score, and (for predict()) applies
# the objective transform — so a scoring call downloads only [n, K] instead
# of [T, n] + a host tile/loop + a re-upload for the transform. Packed trees
# ride as ARGUMENTS (never jit constants), which makes the executables
# shareable process-wide: any Booster with the same shape key — including
# one just unpickled in a serving worker, or a num_iteration sweep — hits
# the same compiled program.


def _pow2_ceil(v: int) -> int:
    """Smallest power of two >= max(1, v)."""
    return 1 << (max(1, int(v)) - 1).bit_length()


def _pack_trees_host(trees: Tree, t_end: int,
                     predict_dtype: str = "f32") -> np.ndarray:
    """Host-side mirror of :func:`pack_trees`: flatten the first ``t_end``
    trees into ONE int32 buffer (bools widened, float/uint bits riding
    bitcast) so the forest upload is a single host->device transfer and the
    executable's tree argument is one flat array.

    ``predict_dtype == "int8"`` shrinks the buffer: the ``leaf_value``
    segment carries per-tree int8-quantized leaves packed four per word
    (``quantize.quantize_leaves`` — the scale math stays in the funnel)
    and the ``[t_end]`` f32 leaf scales ride bitcast at the buffer's
    tail, beside the trees in the same single transfer."""
    parts, tail = [], None
    for name, arr in zip(Tree._fields, trees):
        a = np.asarray(arr)[:t_end].astype(_TREE_FIELD_DTYPES[name],
                                           copy=False)
        if name == "leaf_value" and predict_dtype == "int8":
            q, scale = _quantize.quantize_leaves(a)
            flatq = np.pad(q.reshape(-1), (0, (-q.size) % 4))
            parts.append(np.ascontiguousarray(flatq).view(np.int32))
            tail = np.ascontiguousarray(scale).view(np.int32)
            continue
        if a.dtype == np.bool_:
            a = a.astype(np.int32)
        elif a.dtype != np.int32:
            a = np.ascontiguousarray(a).view(np.int32)
        parts.append(np.ascontiguousarray(a).reshape(-1))
    if tail is not None:
        parts.append(tail)
    return np.concatenate(parts)


def _unpack_trees_device(flat: jnp.ndarray, T: int, M: int, BW: int,
                         predict_dtype: str = "f32") -> Tree:
    """Device-side inverse of :func:`_pack_trees_host` (static slicing —
    traces into pure reshapes/bitcasts, no data movement). Field order,
    shapes and bitcast rules are shared with the host pack/unpack pair
    (``Tree._fields`` / :func:`_tree_field_shape` /
    ``_TREE_FIELD_DTYPES``). The int8 lane unpacks the packed int8 leaf
    segment and dequantizes against the tail scales through the quantize
    funnel — the Tree handed to traversal carries f32 leaves either way
    (the f32-epilogue contract)."""
    fields, off = {}, 0
    for name in Tree._fields:
        shape = _tree_field_shape(name, (T,), M, BW)
        size = int(np.prod(shape, dtype=np.int64))
        if name == "leaf_value" and predict_dtype == "int8":
            nwords = (size + 3) // 4
            q = lax.bitcast_convert_type(flat[off:off + nwords],
                                         jnp.int8).reshape(-1)[:size]
            off += nwords
            scale = lax.bitcast_convert_type(flat[flat.shape[0] - T:],
                                             jnp.float32)
            fields[name] = _quantize.dequantize_leaves_device(
                q.reshape(shape), scale)
            continue
        seg = flat[off:off + size]
        off += size
        dt = _TREE_FIELD_DTYPES[name]
        if dt == np.bool_:
            seg = seg.astype(jnp.bool_)
        elif dt != np.int32:
            seg = lax.bitcast_convert_type(seg, jnp.dtype(dt))
        fields[name] = seg.reshape(shape)
    return Tree(**fields)


def _to_device(x):
    """The predict hot path's ONLY host->device transfer funnel — tests
    shim this to assert exactly one upload per scoring call. Rides the
    placement layer (ROADMAP item 6): placement.to_device is the
    package-wide h2d funnel."""
    return placement.to_device(x)


def _from_device(x) -> np.ndarray:
    """The predict hot path's ONLY device->host transfer funnel — tests
    shim this to assert exactly one download per scoring call."""
    return placement.to_host(x)


# process-wide fused-predictor executable cache. Keyed on shape/config only
# (tree bucket, batch bucket, num_class, transform...), NEVER on a Booster
# instance: a serving worker that unpickles a model, or a sweep re-scoring
# at many num_iteration values, reuses compiled executables instead of
# recompiling per object.
_PREDICT_CACHE: "OrderedDict" = OrderedDict()
_PREDICT_CACHE_MAX = 64
_PREDICT_CACHE_LOCK = threading.Lock()


def _forest_args_nbytes(ent) -> float:
    """Total device bytes a cached forest-argument tuple pins — the
    ``packed_trees`` HBM-ledger claim (None members contribute 0)."""
    return float(sum(getattr(a, "nbytes", 0) or 0 for a in ent
                     if a is not None))


def _cost_summary(compiled) -> dict:
    """FLOPs / bytes-accessed from XLA ``cost_analysis()`` where the
    backend exposes it ({} elsewhere) — the GSPMD observation that what
    got compiled, and how big, is itself a key runtime observable."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        out = {}
        if ca.get("flops") is not None:
            out["flops"] = float(ca["flops"])
        if ca.get("bytes accessed") is not None:
            out["bytes_accessed"] = float(ca["bytes accessed"])
        return out
    except Exception:  # noqa: BLE001 — telemetry must not fail a predict
        return {}


class _ObservedProgram:
    """Cache entry that makes the compile observable.

    ``jax.jit`` compiles lazily on first dispatch, which hides compile
    wall time and the compiled artifact. This wrapper AOT-compiles on the
    first call instead (``lower(*args).compile()`` — exact shapes are
    pinned by the cache key, so one compile serves every call), records a
    flight-recorder compile event with the cache key, wall time, and XLA
    ``cost_analysis()`` FLOPs/bytes, and feeds ``gbdt_compile_seconds``.
    If the AOT path is unavailable it falls back to plain jit dispatch —
    scoring never depends on the observability path.
    """

    __slots__ = ("_jitted", "_key", "_key_hash", "_compiled", "_lock",
                 "_dtype")

    def __init__(self, jitted, key, dtype=None):
        self._jitted = jitted
        self._key = key
        self._key_hash = predict_key_hash(key)
        self._compiled = None
        self._lock = threading.Lock()
        self._dtype = dtype

    @classmethod
    def from_compiled(cls, compiled, key, dtype=None):
        """Wrap an ALREADY-COMPILED executable (the bundle-prewarm path)
        so prewarmed entries get the same call-site roofline timing as
        organically-compiled ones."""
        prog = cls(None, key, dtype=dtype)
        prog._compiled = compiled
        return prog

    def __call__(self, *args):
        fn = self._compiled
        if fn is None:
            fn = self._compile_observed(args)
        if not _metrics.enabled():
            return fn(*args)
        # roofline call-site timer: block on the output so the sample is
        # device wall time, not dispatch time. Cheap in context — every
        # consumer immediately downloads the result (a blocking d2h), so
        # the sync this timer adds was about to happen anyway.
        t0 = time.perf_counter()
        out = fn(*args)
        try:
            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001 — telemetry must not fail a call
            pass
        _roofline.observe_call(self._key_hash, time.perf_counter() - t0)
        return out

    def _compile_observed(self, args):
        # serialized: two serving threads hitting a cold entry must not
        # both pay the multi-second XLA compile (the plain-jit path
        # deduplicated this inside jax's dispatch cache) nor double-count
        # the compile metrics
        with self._lock:
            if self._compiled is not None:
                return self._compiled
            t0 = time.perf_counter()
            cost = {}
            fn = self._jitted.lower(*args).compile()
            cost = _cost_summary(fn)
            dt = time.perf_counter() - t0
            self._compiled = fn
        _metrics.safe_counter("gbdt_compiles_total", cache="predict").inc()
        _metrics.safe_histogram("gbdt_compile_seconds",
                                cache="predict").observe(dt)
        # persistent_cache: the active persistent compile-cache dir
        # (utils/compile_cache). With a warm dir, `seconds` is the disk
        # fetch, not an XLA compile —
        # persistent_compile_cache_hits_total counts those.
        _flight.record("compile", cache="predict", key=repr(self._key),
                       seconds=round(dt, 6),
                       persistent_cache=_compile_cache.cache_dir() or "",
                       **cost)
        try:
            devs = jax.devices()
            if devs:
                _roofline.note_device_kind(
                    getattr(devs[0], "device_kind", None))
        except Exception:  # noqa: BLE001 — peaks degrade to unknown
            pass
        _roofline.register_executable(
            self._key_hash, kind="predict",
            flops=cost.get("flops"),
            bytes_accessed=cost.get("bytes_accessed"),
            compile_seconds=dt, label="gbdt_predict",
            dtype=self._dtype)
        return fn


def _predict_program(key, build, dtype=None):
    """Get-or-build in the bounded process-wide predictor cache, counting
    hits/misses (``gbdt_predict_cache_{hits,misses}_total``)."""
    with _PREDICT_CACHE_LOCK:
        fn = _PREDICT_CACHE.get(key)
        if fn is not None:
            _PREDICT_CACHE.move_to_end(key)
    if fn is None:
        _metrics.safe_counter("gbdt_predict_cache_misses_total").inc()
        with _spans.span("gbdt_predict_build"):
            fn = _ObservedProgram(build(), key, dtype=dtype)
        with _PREDICT_CACHE_LOCK:
            fn = _PREDICT_CACHE.setdefault(key, fn)
            _PREDICT_CACHE.move_to_end(key)
            while len(_PREDICT_CACHE) > _PREDICT_CACHE_MAX:
                _PREDICT_CACHE.popitem(last=False)
    else:
        _metrics.safe_counter("gbdt_predict_cache_hits_total").inc()
    return fn


def preload_predict_program(key, fn, dtype=None) -> bool:
    """Install an ALREADY-COMPILED program under ``key`` — the serving-
    bundle prewarm path (``mmlspark_tpu/bundles``): a worker restarting
    from an AOT bundle populates the predictor cache before its first
    request, so the serving hot path never pays (or even observes) a
    compile. Never clobbers a live entry (a program the process already
    built and warmed beats a deserialized one); returns whether the
    preload took. Counted separately from hits/misses so cold-start
    dashboards can tell prewarmed capacity from organically-warmed."""
    with _PREDICT_CACHE_LOCK:
        if key in _PREDICT_CACHE:
            return False
    # wrap outside the lock (cost_analysis can be slow): prewarmed
    # entries get the same call-site roofline timing as organic ones
    if not isinstance(fn, _ObservedProgram):
        cost = _cost_summary(fn)
        prog = _ObservedProgram.from_compiled(fn, key, dtype=dtype)
        _roofline.register_executable(
            prog._key_hash, kind="predict",
            flops=cost.get("flops"),
            bytes_accessed=cost.get("bytes_accessed"),
            label="gbdt_predict(prewarm)", dtype=dtype)
        fn = prog
    with _PREDICT_CACHE_LOCK:
        if key in _PREDICT_CACHE:      # lost the race while wrapping
            return False
        _PREDICT_CACHE[key] = fn
        while len(_PREDICT_CACHE) > _PREDICT_CACHE_MAX:
            _PREDICT_CACHE.popitem(last=False)
    _metrics.safe_counter("gbdt_predict_cache_preloads_total").inc()
    return True


def predict_key_hash(key) -> str:
    """Stable content hash of a predictor cache key — the name a bundle
    stores an exported executable under. ``repr`` over the key tuple is
    deterministic for everything a key may contain (ints, bools, strings,
    None, nested tuples, and the ``_freeze_kwargs`` ndarray rendering,
    whose payload is raw bytes)."""
    import hashlib
    return hashlib.sha256(repr(key).encode("utf-8")).hexdigest()


class PredictPlan(NamedTuple):
    """One fused predict executable's identity + builder, shared by the
    online dispatch path (:meth:`Booster._predict_device`) and the
    offline AOT bundle builder so the two can never disagree on a cache
    key. ``builder()`` returns the jitted (un-compiled) program."""

    key: tuple
    t_end: int
    n_pad: int
    T_pad: int
    num_features: int
    builder: Callable
    predict_dtype: str = "f32"


def iter_predict_plans(booster: "Booster", batch_sizes,
                       num_iterations=(-1,), transforms=(True,),
                       dtypes=("f32",)):
    """Yield ``(meta, plan)`` for every DISTINCT fused predict
    executable a serving deployment of ``booster`` dispatches over the
    given batch sizes / iteration counts / transform / predict-dtype
    variants. THE one enumeration: the key-manifest export below and
    the bundle builder (``mmlspark_tpu/bundles``) both iterate this, so
    what a bundle pins and what a manifest reports can never drift.
    Batch sizes aliasing into one pow2 bucket dedupe to one plan (the
    executable is shared), and a requested dtype the model degrades
    (``quantize.resolve_predict_dtype``) dedupes into its f32 plan —
    the meta records the EFFECTIVE dtype."""
    seen = set()
    for dt in dtypes:
        for transformed in transforms:
            for it in num_iterations:
                for b in batch_sizes:
                    plan = booster.predict_plan(int(b), int(it),
                                                transformed=transformed,
                                                predict_dtype=dt)
                    if plan.key in seen:
                        continue
                    seen.add(plan.key)
                    yield ({"batch_size": int(b), "num_iteration": int(it),
                            "transformed": bool(transformed),
                            "predict_dtype": plan.predict_dtype}, plan)


def predict_key_manifest(booster: "Booster", batch_sizes,
                         num_iterations=(-1,),
                         transformed: bool = True) -> List[Dict]:
    """Key-manifest export: the (batch bucket x iteration) predictor
    cache keys a serving deployment of ``booster`` will dispatch to —
    what the bundle builder enumerates and what its MANIFEST.json pins."""
    return [{**meta, "n_pad": plan.n_pad, "t_pad": plan.T_pad,
             "key_hash": predict_key_hash(plan.key)}
            for meta, plan in iter_predict_plans(
                booster, batch_sizes, num_iterations,
                transforms=(transformed,))]


def _freeze_kwargs(kwargs: dict):
    """Hashable rendering of objective kwargs for the executable-cache
    key. JSON round-trips turn tuples into lists (e.g. a ranker's
    label_gain), which would make the key unhashable — values are frozen
    structurally, never passed back to the objective (the builder uses
    the booster's own kwargs for that)."""
    def freeze(v):
        if isinstance(v, (list, tuple)):
            return tuple(freeze(x) for x in v)
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        if isinstance(v, np.ndarray):
            return ("ndarray", v.dtype.str, v.shape, v.tobytes())
        return v
    return tuple(sorted((k, freeze(v)) for k, v in kwargs.items()))


def _build_predict_program(T_pad: int, M: int, BW: int, depth_cap: int,
                           K: int, cat_max_bin: int, transform,
                           predict_dtype: str = "f32"):
    """Build the fused device-resident scoring program.

    ``run(packed, thr, base, active, is_cat, mdec, X)`` evaluates all
    ``T_pad`` trees, masks out trees past ``t_end`` via ``active`` (so one
    executable serves every t_end inside the bucket), reduces per class,
    adds the base score and — when ``transform`` (a traceable raw->
    prediction function, see ``objectives.score_transform``) is set —
    applies the objective transform, all inside ONE jitted program.
    ``is_cat`` / ``mdec`` are passed as ``None`` when absent (the key
    distinguishes those variants).

    ``predict_dtype`` selects the traversal lane (ROADMAP item 3):
    ``int8`` compares uint8 bin-id features against uint8 bin-id
    thresholds (routing bit-exact vs f32 — see ``quantize.py``) over
    int8-packed leaves; ``bf16`` compares bfloat16 features/thresholds.
    Both keep the epilogue — leaf gather, per-class sum, base score,
    transform — in f32."""

    def run(packed, thr, base, active, is_cat, mdec, X):
        trees = _unpack_trees_device(packed, T_pad, M, BW,
                                     predict_dtype=predict_dtype)
        leaf = predict_forest_raw(trees, thr, X, depth_cap, is_cat=is_cat,
                                  cat_max_bin=cat_max_bin,
                                  missing_dec=mdec)            # [T_pad, n]
        masked = leaf * active[:, None]
        if T_pad % K == 0:
            # tree t scores class t % K: [T_pad/K, K, n] groups each
            # class's trees in one reshaped axis — same mapping as the
            # old host loop's per_tree[k::K].sum(0)
            per_class = masked.reshape(T_pad // K, K,
                                       masked.shape[1]).sum(axis=0)
        else:                       # defensive: partial final iteration
            onehot = jax.nn.one_hot(jnp.arange(T_pad) % K, K,
                                    dtype=masked.dtype)
            per_class = jnp.einsum("tk,tn->kn", onehot, masked)
        raw = per_class.T + base[None, :]                      # [n, K]
        return raw if transform is None else transform(raw)

    return jax.jit(run)


# --- device-side synthesis of row-shaped defaults ---------------------------
# The validity mask, default unit weights, and base-score broadcast are pure
# functions of scalars; generating them on device avoids three dataset-sized
# host->device transfers per training call.


def _device_validity_mask(n: int, n_pad: int, mesh: Mesh):
    fn = _cached_program(("synth_vmask", n, n_pad, mesh), lambda: jax.jit(
        lambda: (jnp.arange(n_pad) < n).astype(jnp.float32),
        out_shardings=placement.row_sharding(mesh)))
    return fn()


def _device_tile_scores(base_d, n_pad: int, K: int, mesh: Mesh):
    fn = _cached_program(("synth_scores", n_pad, K, mesh), lambda: jax.jit(
        lambda b: jnp.broadcast_to(
            b[None, :].astype(jnp.float32), (n_pad, K)),
        out_shardings=placement.row_sharding(mesh, ndim=2)))
    return fn(base_d)


def _bin_program(x_shape, max_bin: int, mesh: Mesh, bin_dtype=jnp.int32):
    return _cached_program(
        ("bin_cols", x_shape, max_bin, mesh, jnp.dtype(bin_dtype).name),
        lambda: jax.jit(shard_map(
            lambda X, ub: bin_cols_device(X, ub, out_dtype=bin_dtype),
            mesh=mesh,
            in_specs=(P("data", None), P()), out_specs=P(None, "data"),
            check_vma=False)))


def _validate_bin_dtype(bin_dtype, max_bin: int):
    """Bin-id storage dtype: int32 (default), int16, uint8 or int8. Bin
    ids are < max_bin, so narrow storage is lossless within range; it
    shrinks the HBM-resident dataset 2x/4x — the lever that fits
    Criteo-scale binned matrices on a v5e pod (docs/performance.md
    "scaling"). int8 (ids < 128, i.e. max_bin <= 128) matches the
    quantized predict lane's signed-byte staging for frameworks that
    want one dtype end to end. Kernels and routing widen per block in
    VMEM, never in HBM."""
    bd = jnp.dtype(bin_dtype)
    limits = {"int32": 1 << 31, "int16": 1 << 15, "uint8": 256,
              "int8": 128}
    if bd.name not in limits:
        raise ValueError(
            f"bin_dtype must be one of {sorted(limits)}, got {bd.name}")
    if max_bin > limits[bd.name]:
        raise ValueError(
            f"bin_dtype={bd.name} holds bin ids < {limits[bd.name]}, "
            f"but max_bin={max_bin}")
    return bd


class LightGBMDataset:
    """Pre-binned, device-resident GBDT training dataset: bin once, train many.

    Parity with the reference's native dataset construction
    (lightgbm/LightGBMDataset.scala:70-159, built via LGBM_DatasetCreateFromMat
    — LightGBMUtils.scala:227): the reference builds the binned native dataset
    once per partition before the iteration loop ever runs. Here construction
    quantile-bins on device into the column-major ``[F, n_pad]`` layout and
    every ``train_booster(dataset=...)`` call starts from that device matrix —
    the expensive ingest (binner fit + feature-matrix transfer + binning) is
    paid once, not per training run. This also matches how LightGBM itself is
    measured: Dataset construction is one-time setup, train() is the timed
    phase.
    """

    def __init__(self, binner, Xbt_d, y_d, w_d, vmask_d, n: int, n_pad: int,
                 mesh: Mesh, max_bin: int, categorical_features):
        self.binner = binner
        self.Xbt_d = Xbt_d
        self.y_d = y_d
        self.w_d = w_d
        self.vmask_d = vmask_d
        self.n = n
        self.n_pad = n_pad
        self.mesh = mesh
        self.max_bin = max_bin
        self.categorical_features = tuple(
            int(i) for i in categorical_features)

    @property
    def num_features(self) -> int:
        return int(self.Xbt_d.shape[0])

    def eval_weight(self):
        """The rows' weight with validity folded in (padding and dead rows
        weigh nothing), as a metric over these rows takes it. Default unit
        weights ARE the validity mask."""
        if self.w_d is self.vmask_d:
            return self.w_d
        return _cached_program(
            ("eval_weight", self.w_d.shape, self.mesh),
            lambda: jax.jit(jnp.multiply))(self.w_d, self.vmask_d)

    @classmethod
    def construct(cls, X=None, y=None, weight=None, *, max_bin: int = 255,
                  bin_sample_count: int = 200_000, seed: int = 0,
                  categorical_features=(), mesh: Optional[Mesh] = None,
                  row_valid: Optional[np.ndarray] = None,
                  bin_dtype=None, path=None, label_path=None,
                  weight_path=None, chunk_rows: Optional[int] = None,
                  max_bin_by_feature=None,
                  reference: Optional["LightGBMDataset"] = None
                  ) -> "LightGBMDataset":
        """``reference`` (LightGBM's ``Dataset(reference=train)``): bin these
        rows with that dataset's binner, into its storage dtype, on its mesh,
        with no binner fit: how a validation set is built once, for every
        fit against ``reference`` to hold (``train_booster(valid_set=...)``).
        """
        if reference is not None:
            if path is not None:
                raise ValueError("reference= takes in-memory arrays")
            max_bin, mesh = reference.max_bin, reference.mesh
            categorical_features = reference.categorical_features
            bin_dtype = reference.Xbt_d.dtype
        if path is None and (label_path is not None
                             or weight_path is not None
                             or chunk_rows is not None):
            raise ValueError(
                "label_path/weight_path/chunk_rows only apply with path= "
                "(out-of-core); for in-memory arrays pass y/weight directly")
        if path is not None:
            # out-of-core: stream file shards through chunked device binning
            # (host peak = one chunk + the binner sample). The reference's
            # equivalent is Spark partition files feeding the chunked native
            # dataset (lightgbm/LightGBMUtils.scala:201-265).
            if X is not None or y is not None or weight is not None:
                raise ValueError(
                    "pass either in-memory arrays or path=..., not both")
            if label_path is None:
                raise ValueError("path= requires label_path=")
            if row_valid is not None:
                raise ValueError("row_valid is not supported with path= "
                                 "(ranker group padding is in-memory only)")
            from .ingest import construct_from_files
            # out-of-core is the large-n regime: default bin storage narrows
            # to uint8 when max_bin allows; an explicit bin_dtype (including
            # 'int32') is honored as given.
            if bin_dtype is None:
                bin_dtype = "uint8" if max_bin <= 256 else "int32"
            _validate_bin_dtype(bin_dtype, max_bin)
            return construct_from_files(
                path, label_path, weight_path, max_bin=max_bin,
                bin_sample_count=bin_sample_count, seed=seed,
                categorical_features=categorical_features, mesh=mesh,
                bin_dtype=bin_dtype,
                chunk_rows=262_144 if chunk_rows is None else chunk_rows,
                max_bin_by_feature=max_bin_by_feature)
        if X is None or y is None:
            raise ValueError(
                "construct needs in-memory arrays (X, y) or file shards "
                "(path=..., label_path=...)")
        mesh = mesh or meshlib.get_default_mesh()
        X = np.asarray(X, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        n, F = X.shape
        if reference is not None and F != reference.num_features:
            raise ValueError(
                f"the reference dataset has {reference.num_features} "
                f"features, these rows {F}")
        bad_cats = [int(i) for i in categorical_features
                    if not (0 <= int(i) < F)]
        if bad_cats:
            raise ValueError(
                f"categorical_features indexes {bad_cats} out of range for "
                f"{F} features")
        bd = _validate_bin_dtype("int32" if bin_dtype is None else bin_dtype,
                                 max_bin)
        with _spans.span("gbdt_dataset", rows=n, features=F) as ds, \
                _Phases(ds) as phases:
            phases.enter("gbdt_binner_fit")
            binner = reference.binner if reference is not None else \
                QuantileBinner(max_bin, bin_sample_count, seed,
                               categorical_features,
                               max_bin_by_feature).fit(X)
            # transfers are asynchronous: what of the raw matrix's upload
            # outlasts this phase shows in gbdt_dataset_bin, which waits
            phases.enter("gbdt_dataset_xfer")
            # placement decision (observable): dataset rows are batch-dim
            # sharded over the mesh's data axis when it has >1 shard; the
            # note carries the binned matrix's storage dtype so the flight
            # ring shows how wide the HBM-resident dataset landed
            placement.plan_for("gbdt.ingest", mesh=mesh, rows=n,
                               dtype=bd.name)
            # Binning runs ON DEVICE, producing the column-major
            # [F, n_local] layout tree growth consumes (the host
            # searchsorted pass measured 1.6 s at the 1Mx28 bench shape vs
            # ~ms of VPU compare-sums; raw and binned rows are the same byte
            # count so the transfer is unchanged). Padding rows bin to
            # garbage but carry vmask 0 downstream.
            X_d, _ = placement.shard_rows(X, mesh)
            phases.enter("gbdt_dataset_bin")
            bin_fn = _bin_program(X_d.shape, max_bin, mesh, bin_dtype=bd)
            n_pad = X_d.shape[0]
            Xbt_d = bin_fn(X_d, jnp.asarray(binner.upper_bounds))
            # the raw copy served only to produce the binned matrix: free
            # its HBM now or both dataset-sized buffers stay live for the
            # whole run
            Xbt_d.block_until_ready()
            X_d.delete()
            del X_d
            phases.enter("gbdt_dataset_aux")
            y_d, _ = placement.shard_rows(y, mesh)
            if row_valid is not None:
                # in-group padding rows (ranker) are dead for
                # counts/histograms
                vmask = meshlib.validity_mask(n, n_pad)
                vmask[:n] *= np.asarray(row_valid, np.float32)
                vmask_d, _ = placement.shard_rows(vmask, mesh)
            else:
                vmask_d = _device_validity_mask(n, n_pad, mesh)
            if weight is not None:
                w_d, _ = placement.shard_rows(
                    np.asarray(weight, np.float32), mesh)
            else:
                # default unit weights with zeros on padding rows — exactly
                # the validity mask, so no second array is synthesized or
                # stored
                w_d = vmask_d
            return cls(binner, Xbt_d, y_d, w_d, vmask_d, n, n_pad, mesh,
                       max_bin, categorical_features)


def _with_tree_defaults(fields: Dict) -> Dict:
    """Backfill tree fields added after format v1 (e.g. node_value) so models
    saved by older versions still load; node_value falls back to leaf_value
    (SHAP contributions then attribute only at leaves)."""
    if "node_value" not in fields:
        fields["node_value"] = np.asarray(fields["leaf_value"])
    if "cat_bitset" not in fields:
        shape = np.asarray(fields["feat"]).shape   # [T, M] or [M]
        fields["cat_bitset"] = np.zeros((*shape, 1), np.uint32)
    else:
        fields["cat_bitset"] = np.asarray(
            fields["cat_bitset"]).astype(np.uint32)
    return fields


def _densify(X):
    """Accept scipy.sparse CSR/CSC input (LGBM_DatasetCreateFromCSR parity,
    reference: lightgbm/LightGBMUtils.scala:227): densify in row blocks so
    peak host memory is the output array plus one block, then feed the
    standard dense path (pad/densify-per-shard is the TPU-native layout —
    histograms need dense bin matrices on the MXU anyway)."""
    from ...core.dataset import _is_sparse
    if not _is_sparse(X):
        return X
    X = X.tocsr()
    n, F = X.shape
    out = np.zeros((n, F), dtype=np.float32)
    step = max(1, (8 << 20) // max(F * 4, 1))
    for start in range(0, n, step):
        out[start:start + step] = X[start:start + step].toarray()
    return out


class Booster:
    """A trained GBDT ensemble (stacked fixed-shape trees)."""

    def __init__(self, trees: Tree, thr_raw: np.ndarray, num_class: int,
                 base_score: np.ndarray, objective: str, depth_cap: int,
                 binner_state: dict, best_iteration: int = -1,
                 eval_history: Optional[Dict[str, List[float]]] = None,
                 objective_kwargs: Optional[dict] = None):
        self.trees = jax.tree_util.tree_map(np.asarray, trees)  # [T*K, M] arrays
        self.thr_raw = np.asarray(thr_raw)
        self.num_class = int(num_class)
        self.base_score = np.asarray(base_score, dtype=np.float32).reshape(-1)
        self.objective = objective
        self.objective_kwargs = objective_kwargs or {}
        self.depth_cap = int(depth_cap)
        self.binner_state = binner_state
        self.best_iteration = int(best_iteration)
        self.eval_history = eval_history or {}
        # Per-node LightGBM decision_type bytes [T, M] (missing-value
        # routing: bit 1 default-left, bits 2-3 missing type), set only by
        # the native-model import path. None = the framework's own training
        # semantics (NaN routes left — decision_type 10), which the fast
        # `~(x > thr)` routing implements directly.
        self.missing_dec: Optional[np.ndarray] = None

    # -- inference -------------------------------------------------------------
    @property
    def num_trees(self) -> int:
        return int(self.trees.feat.shape[0])

    @property
    def num_iterations(self) -> int:
        return self.num_trees // self.num_class

    def __getstate__(self):
        # device-resident predictor state (uploaded tree buffers, active
        # masks) is rebuilt on demand and never pickled; the COMPILED
        # executables live in the process-wide _PREDICT_CACHE keyed by
        # shape, so an unpickled model in a serving worker reuses them
        # without recompiling
        d = dict(self.__dict__)
        d.pop("_dev_forest", None)
        d.pop("_dev_active", None)
        d.pop("_predict_fn", None)    # legacy per-instance jit cache
        return d

    def _obj(self) -> Objective:
        return get_objective(self.objective, self.num_class, **self.objective_kwargs)

    def _cat_max_idx(self) -> int:
        """Largest valid category bin id (the binner's catch-all bin)."""
        mb = self.binner_state.get("max_bin") or 0
        if mb > 0:
            return mb - 1
        return int(np.asarray(self.trees.cat_bitset).shape[-1]) * 32 - 1

    def _cat_strict(self) -> bool:
        """Imported stock-LightGBM models (no binner): FindInBitset
        semantics — out-of-range/NaN categories route right."""
        return (self.binner_state.get("max_bin") or 0) <= 0

    def _is_cat(self):
        """[F] bool device mask of categorical features, or None."""
        cats = self.binner_state.get("categorical_features") or ()
        F = self.binner_state["upper_bounds"].shape[0]
        cats = [int(i) for i in cats if 0 <= int(i) < F]
        if not cats:
            return None
        m = np.zeros(F, dtype=bool)
        m[np.asarray(cats, dtype=int)] = True
        return jnp.asarray(m)

    def _tree_bucket(self, t_end: int) -> int:
        """Tree-count bucket for the executable cache: the full model keeps
        its exact shape (the serving hot path must not pay padded-forest
        compute), partial t_end — num_iteration sweeps, best_iteration
        scoring — rounds the iteration count up to a power of two so a
        sweep hits log2 executables instead of one per value. Trees past
        ``t_end`` inside the bucket are masked by the ``active`` argument,
        so bucketing never changes results."""
        T_full = self.num_trees
        if t_end >= T_full:
            return T_full
        bucket = self.num_class * _pow2_ceil(t_end // self.num_class)
        return T_full if bucket >= T_full else bucket

    def _device_forest_args(self, T_pad: int, predict_dtype: str = "f32"):
        """Device-RESIDENT forest arguments for the first ``T_pad`` trees:
        (packed trees, thresholds, base score, categorical mask, missing
        decisions) — uploaded once per bucket, cached on the instance
        (dropped by ``__getstate__``), and passed as jit ARGUMENTS so the
        compiled program itself stays model-independent. Narrow predict
        lanes cache their own entries: the int8 lane packs int8 leaves
        and uint8 bin-id thresholds (quantize funnel), the bf16 lane
        narrows thresholds — so the ``packed_trees`` HBM claim shrinks
        with the lane."""
        cache = self.__dict__.setdefault("_dev_forest", OrderedDict())
        ck = (T_pad, predict_dtype)
        ent = cache.get(ck)
        if ent is None:
            packed = _pack_trees_host(self.trees, T_pad, predict_dtype)
            thr = np.ascontiguousarray(
                np.asarray(self.thr_raw, np.float32)[:T_pad])
            if predict_dtype == "int8":
                thr = _quantize.quantize_thresholds(
                    thr, np.asarray(self.trees.feat)[:T_pad],
                    _quantize.feature_bounds(self.binner_state))
            elif predict_dtype == "bf16":
                thr = _quantize.cast_thresholds_bf16(thr)
            is_cat = self._is_cat()
            mdec = (None if self.missing_dec is None
                    else jnp.asarray(
                        np.ascontiguousarray(self.missing_dec[:T_pad])))
            ent = (jnp.asarray(packed), jnp.asarray(thr),
                   jnp.asarray(self.base_score), is_cat, mdec)
            _hbm.claim("packed_trees", _forest_args_nbytes(ent))
            # bounded LRU: each entry pins a device tree buffer, so a
            # learning-curve sweep over every t_end must not pin O(T^2)
            cache[ck] = ent
            while len(cache) > 4:
                _k, old = cache.popitem(last=False)
                _hbm.release("packed_trees", _forest_args_nbytes(old))
        else:
            cache.move_to_end(ck)
        return ent

    def _device_active(self, T_pad: int, t_end: int):
        """[T_pad] f32 device mask selecting trees below ``t_end``."""
        cache = self.__dict__.setdefault("_dev_active", OrderedDict())
        key = (T_pad, t_end)
        a = cache.get(key)
        if a is None:
            a = jnp.asarray((np.arange(T_pad) < t_end)
                            .astype(np.float32))
            cache[key] = a
            while len(cache) > 8:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return a

    def resolved_predict_dtype(self, requested: Optional[str] = None) -> str:
        """The effective predict lane for THIS model: delegates to the
        quantize funnel's resolver with this booster's capability flags
        (imported missing-value semantics, binner grid width). What a
        serving worker pins once at startup and surfaces on ``/varz`` —
        the same resolution :meth:`predict_plan` performs per call, so
        the pinned lane and the cache key can never disagree."""
        return _quantize.resolve_predict_dtype(
            requested, has_mdec=self.missing_dec is not None,
            max_bin=int(self.binner_state.get("max_bin") or 0))

    def predict_plan(self, n: int, num_iteration: int = -1,
                     transformed: bool = True,
                     num_features: Optional[int] = None,
                     predict_dtype: Optional[str] = None) -> "PredictPlan":
        """The fused predict executable a batch of ``n`` rows dispatches
        to: its process-wide cache key plus everything needed to build
        (or AOT-export) the program WITHOUT running it.

        This is the one place the predictor cache key is computed —
        :meth:`_predict_device` (the online hot path) and the offline
        serving-bundle builder (``mmlspark_tpu/bundles``) both call it,
        so a key manifested into a bundle at build time is byte-identical
        to the key the restarted worker looks up at serve time. Host-only:
        no device transfer and no compile happen here."""
        if num_iteration is None or num_iteration < 0:
            num_iteration = self.num_iterations
        t_end = min(num_iteration * self.num_class, self.num_trees)
        # row bucket for SMALL batches only: serving's varying micro-batch
        # sizes hit a bounded set of cached executables instead of one
        # trace per size. The bucket ladder is resolved HERE, before the
        # cache key below (the PR 4 rule, lint-anchored): the auto-tuner's
        # measured ladder (tuning site 1 — rungs at the observed
        # workload's batch-size percentiles, pow2 above them) when one is
        # decided, else the static pow2 grid. Large batch scoring keeps
        # its exact shape — padding 600k rows to 1M would waste up to 2x
        # forest compute.
        ladder = _tuning.resolve_bucket_ladder()
        if 0 < n <= 8192:
            n_pad = (_tuning.ladder_pad(n, ladder) if ladder
                     else 1 << (n - 1).bit_length())
        else:
            n_pad = max(n, 1)
        T_pad = self._tree_bucket(t_end)
        M = int(np.asarray(self.trees.feat).shape[1])
        BW = int(np.asarray(self.trees.cat_bitset).shape[-1])
        cat_max_bin = int(self.binner_state.get("max_bin") or 0)
        F_bin = int(self.binner_state["upper_bounds"].shape[0])
        if num_features is None:
            num_features = F_bin
        # the dtype lane is resolved HERE, before the cache key exists
        # (the PR 4 rule, lint-anchored): env/explicit resolution and
        # capability degrades live in the quantize funnel, so a key can
        # never contain an unresolved or unsupported dtype
        predict_dtype = _quantize.resolve_predict_dtype(
            predict_dtype, has_mdec=self.missing_dec is not None,
            max_bin=cat_max_bin)
        spec_key = transform = None
        if transformed:
            spec_key = (self.objective, self.num_class,
                        _freeze_kwargs(self.objective_kwargs))
            transform = score_transform(self.objective, self.num_class,
                                        **self.objective_kwargs)
        # mirrors _is_cat()/_device_forest_args WITHOUT touching the
        # device: the key only records whether the optional args exist
        has_cat = any(0 <= int(i) < F_bin for i in
                      (self.binner_state.get("categorical_features") or ()))
        has_mdec = self.missing_dec is not None
        key = (T_pad, M, BW, n_pad, num_features, self.num_class,
               self.depth_cap, cat_max_bin, has_cat, has_mdec,
               predict_dtype, spec_key)
        depth_cap, K = self.depth_cap, self.num_class
        return PredictPlan(
            key=key, t_end=t_end, n_pad=n_pad, T_pad=T_pad,
            num_features=num_features,
            builder=lambda: _build_predict_program(
                T_pad, M, BW, depth_cap, K, cat_max_bin, transform,
                predict_dtype),
            predict_dtype=predict_dtype)

    def predict_plan_args(self, plan: "PredictPlan"):
        """The exact argument tuple ``plan``'s program is called with —
        real device forest args plus a shape-only stand-in for the
        feature batch. What the bundle builder traces/AOT-lowers against
        (and the prewarm path compiles deserialized exports against)."""
        packed, thr, base, is_cat, mdec = self._device_forest_args(
            plan.T_pad, plan.predict_dtype)
        active = self._device_active(plan.T_pad, plan.t_end)
        x_sds = jax.ShapeDtypeStruct(
            (plan.n_pad, plan.num_features),
            jnp.dtype(_quantize.staging_dtype(plan.predict_dtype)))
        return (packed, thr, base, active, is_cat, mdec, x_sds)

    def _predict_device(self, X: np.ndarray, num_iteration: int,
                        transformed: bool,
                        predict_dtype: Optional[str] = None) -> np.ndarray:
        """Shared device-resident scoring driver for predict/predict_raw.

        Steady state (device args warm) a call is exactly ONE host->device
        transfer (the feature batch, via :func:`_to_device`) and ONE
        device->host transfer (the ``[n, K]`` result, via
        :func:`_from_device`): tree-sum, base-score add and the objective
        transform are fused into the cached executable.

        Narrow lanes stage the batch in the lane's dtype before the
        upload (quartering/halving the h2d bytes); input ALREADY in the
        staged dtype — async-serving slot-table rows quantized at
        admission — passes through untouched.
        """
        _compile_cache.ensure()
        # placement decision (deduped flight event): the fused predictor
        # replicates — its executable cache is keyed on exact batch shapes
        placement.plan_for("gbdt.predict", replicate=True)
        X = np.asarray(X)
        n = X.shape[0]
        plan = self.predict_plan(n, num_iteration, transformed,
                                 num_features=X.shape[1],
                                 predict_dtype=predict_dtype)
        if X.dtype != _quantize.staging_dtype(plan.predict_dtype):
            if plan.predict_dtype == "int8":
                X = _quantize.quantize_features(
                    X, _quantize.feature_bounds(self.binner_state))
            elif plan.predict_dtype == "bf16":
                X = _quantize.cast_features_bf16(X)
            else:
                X = np.asarray(X, dtype=np.float32)
        packed, thr, base, is_cat, mdec = self._device_forest_args(
            plan.T_pad, plan.predict_dtype)
        active = self._device_active(plan.T_pad, plan.t_end)
        fn = _predict_program(plan.key, plan.builder,
                              dtype=plan.predict_dtype)
        n_pad = plan.n_pad
        Xp = np.pad(X, ((0, n_pad - n), (0, 0))) if n_pad != n else X
        out = fn(packed, thr, base, active, is_cat, mdec, _to_device(Xp))
        return _from_device(out)[:n]

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1,
                    predict_dtype: Optional[str] = None) -> np.ndarray:
        """Raw margin scores: [n, num_class] (num_class=1 for
        binary/regression). Device-resident end to end: the per-class
        tree-sum and base-score add run inside the compiled forest program
        (see :meth:`_predict_device`), downloading only ``[n, K]``."""
        return self._predict_device(X, num_iteration, transformed=False,
                                    predict_dtype=predict_dtype)

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                predict_dtype: Optional[str] = None) -> np.ndarray:
        """Transformed prediction (probability for binary/multiclass).
        The sigmoid/softmax/exp transform is fused into the same compiled
        program as the forest evaluation — no raw-score download and
        re-upload between the two. ``predict_dtype`` selects the scoring
        lane (``f32``/``bf16``/``int8``; None reads
        ``MMLSPARK_TPU_PREDICT_DTYPE``) — see ``quantize.py``."""
        return self._predict_device(X, num_iteration, transformed=True,
                                    predict_dtype=predict_dtype)

    def predict_streamed(self, source, *, chunk_rows: int = 262_144,
                         out_dir=None, num_iteration: int = -1,
                         raw: bool = False):
        """Score ``.npy`` feature shards in bounded row chunks —
        larger-than-RAM inference. Each chunk runs exactly
        :meth:`predict` / :meth:`predict_raw`, so streamed outputs equal
        in-memory outputs bit-for-bit. The reference gets this shape for
        free from Spark partition streaming
        (io/binary/BinaryFileReader.scala:20 feeding the native scorer,
        lightgbm/LightGBMBooster.scala:250); here it is an explicit
        bounded-chunk loop (io/streaming.py). Returns concatenated scores,
        or output shard paths with ``out_dir``.
        """
        from ...io.streaming import stream_apply

        if raw:
            fn = lambda c: self.predict_raw(c, num_iteration)   # noqa: E731
        else:
            fn = lambda c: self.predict(c, num_iteration)       # noqa: E731
        return stream_apply(source, fn, chunk_rows=chunk_rows,
                            out_dir=out_dir)

    def predict_contrib_streamed(self, source, *,
                                 chunk_rows: int = 16_384, out_dir=None,
                                 method: str = "treeshap"):
        """Per-feature contributions over ``.npy`` feature shards in
        bounded row chunks — larger-than-RAM explanation. Each chunk runs
        exactly :meth:`predict_contrib` (TreeSHAP is row-independent, so
        streamed == in-memory bit-for-bit); the output is [n, (F+1)*K],
        F+1 times wider than the input, hence the smaller default chunk.
        Reference bar: featuresShapCol over streamed partitions
        (lightgbm/LightGBMBooster.scala:250-269). Returns concatenated
        contributions, or output shard paths with ``out_dir``.
        """
        from ...io.streaming import stream_apply

        if method not in ("treeshap", "saabas"):
            # validate BEFORE stream_apply clears any existing out_dir
            # shards: a typo'd method must not destroy a prior run's output
            raise ValueError(
                f"unknown contribution method {method!r}; expected "
                "'treeshap' or 'saabas'")
        return stream_apply(
            source, lambda c: self.predict_contrib(c, method=method),
            chunk_rows=chunk_rows, out_dir=out_dir)

    def _check_missing_routing(self, X: np.ndarray) -> None:
        """The SHAP/leaf paths route NaN left unconditionally. For imported
        models storing different missing handling (missing_dec set), inputs
        that would hit those rules must not silently diverge from the
        decision_type-aware predict() path."""
        if self.missing_dec is None:
            return
        # check the float32 view the SHAP/leaf paths actually traverse:
        # f64 values that underflow to 0.0 in f32 must not slip the guard
        X = np.asarray(X, dtype=np.float32)
        mt = (self.missing_dec >> 2) & 3
        internal = ~np.asarray(self.trees.is_leaf)
        if (bool(((mt == 1) & internal).any())
                and (np.abs(X) <= 1e-35).any()):
            raise NotImplementedError(
                "predict_contrib/predict_leaf do not implement "
                "zero-as-missing routing for imported models; use "
                "predict()/predict_raw()")
        if np.isnan(X).any():
            raise NotImplementedError(
                "predict_contrib/predict_leaf route NaN left "
                "unconditionally, but this imported model stores different "
                "missing handling; impute NaNs or use "
                "predict()/predict_raw()")

    def predict_contrib(self, X: np.ndarray,
                        method: str = "treeshap") -> np.ndarray:
        """Per-feature contributions ([n, (F+1) * num_class]; the last slot
        of each class block is the bias/expected value).

        ``method="treeshap"`` (default — parity with the reference's
        ``featuresShapCol``, lightgbm/LightGBMBooster.scala:250-269, which
        rides LightGBM's native TreeSHAP): exact Shapley values of the
        cover-conditional value function. Runs the fixed-shape per-leaf
        device formulation (:mod:`.treeshap_device` — leaf paths folded on
        host, all O(depth^2) Shapley-weight work jitted and vectorized
        over leaves x rows); set ``MMLSPARK_TPU_SHAP_HOST=1`` to force the
        reference host recursion (:mod:`.treeshap`, Lundberg Alg. 2) the
        device path is pinned against.

        ``method="saabas"``: fast on-device path attribution — walking
        root->leaf attributes the change in expected node value to the
        split feature. Sums to the same prediction but is NOT Shapley on
        correlated features; kept as the throughput option.
        """
        self._check_missing_routing(X)
        if method == "treeshap":
            # default by backend: the fixed-shape device program is built
            # for TPU (tiny fused VPU/MXU ops, one scanned executable);
            # measured on the XLA CPU backend it loses to the numpy host
            # recursion, so CPU defaults to host. Env overrides both ways.
            force_host = os.environ.get("MMLSPARK_TPU_SHAP_HOST") == "1"
            force_dev = os.environ.get("MMLSPARK_TPU_SHAP_DEVICE") == "1"
            on_accel = jax.devices()[0].platform not in ("cpu",)
            if force_dev or (on_accel and not force_host):
                from .treeshap_device import shap_values_device
                return shap_values_device(self, X)
            from .treeshap import shap_values
            return shap_values(self, X)
        if method != "saabas":
            raise ValueError(
                f"unknown contribution method {method!r}: use 'treeshap' "
                "(exact, host) or 'saabas' (approximate, device)")
        X = np.asarray(X, dtype=np.float32)
        Xd = jnp.asarray(X)
        trees = jax.tree_util.tree_map(jnp.asarray, self.trees)
        thr = jnp.asarray(self.thr_raw)
        is_cat = self._is_cat()
        cat_max_idx = self._cat_max_idx()
        cat_strict = self._cat_strict()
        n, F = X.shape
        K = self.num_class
        T = self.num_trees
        class_of_tree = jnp.arange(T, dtype=jnp.int32) % K

        def scan_body(carry, xs):
            # accumulate per-class sums: peak memory [K, n, F], not [T, n, F]
            csum, rsum = carry
            ts, thr_t, k = xs
            node = jnp.zeros(n, dtype=jnp.int32)
            contrib = jnp.zeros((n, F), dtype=jnp.float32)

            def body(_, st):
                node, contrib = st
                f = ts.feat[node]
                x = jnp.take_along_axis(Xd, f[:, None], axis=1)[:, 0]
                go_left = ~(x > thr_t[node])
                if is_cat is not None:
                    from .growth import cat_member
                    go_left = jnp.where(
                        is_cat[f],
                        cat_member(ts.cat_bitset[node], x, cat_max_idx,
                                   cat_strict),
                        go_left)
                nxt = jnp.where(go_left, ts.left[node], ts.right[node])
                internal = ~ts.is_leaf[node]
                delta = ts.node_value[nxt] - ts.node_value[node]
                contrib = contrib.at[jnp.arange(n), f].add(
                    jnp.where(internal, delta, 0.0))
                return jnp.where(internal, nxt, node), contrib

            _, contrib = jax.lax.fori_loop(0, self.depth_cap, body,
                                           (node, contrib))
            return (csum.at[k].add(contrib),
                    rsum.at[k].add(ts.node_value[0])), None

        init = (jnp.zeros((K, n, F), jnp.float32), jnp.zeros(K, jnp.float32))
        (csum, rsum), _ = jax.lax.scan(scan_body, init,
                                       (trees, thr, class_of_tree))
        csum, rsum = np.asarray(csum), np.asarray(rsum)
        out = np.zeros((n, (F + 1) * K), dtype=np.float32)
        for k in range(K):
            out[:, k * (F + 1):k * (F + 1) + F] = csum[k]
            out[:, k * (F + 1) + F] = self.base_score[k] + rsum[k]
        return out

    def predict_leaf(self, X: np.ndarray) -> np.ndarray:
        """Per-tree leaf index for each row: [n, T] (predLeaf parity,
        reference: lightgbm/LightGBMBooster.scala:250-269)."""
        X32 = np.asarray(X, dtype=np.float32)
        self._check_missing_routing(X32)
        X = jnp.asarray(X32)
        trees = jax.tree_util.tree_map(jnp.asarray, self.trees)
        n = X.shape[0]

        is_cat = self._is_cat()
        cat_max_idx = self._cat_max_idx()
        cat_strict = self._cat_strict()

        def one_tree(ts, thr):
            node = jnp.zeros(n, dtype=jnp.int32)

            def body(_, node):
                f = ts.feat[node]
                x = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
                go_left = ~(x > thr[node])
                if is_cat is not None:
                    from .growth import cat_member
                    go_left = jnp.where(
                        is_cat[f],
                        cat_member(ts.cat_bitset[node], x, cat_max_idx,
                                   cat_strict),
                        go_left)
                nxt = jnp.where(go_left, ts.left[node], ts.right[node])
                return jnp.where(ts.is_leaf[node], node, nxt)

            return jax.lax.fori_loop(0, self.depth_cap, body, node)

        return np.asarray(jax.vmap(one_tree)(trees, jnp.asarray(self.thr_raw))).T

    # -- introspection -----------------------------------------------------------
    def feature_importances(self, importance_type: str = "split") -> np.ndarray:
        """Per-feature importances (reference: LightGBMBooster.scala:306)."""
        F = self.binner_state["upper_bounds"].shape[0]
        out = np.zeros(F, dtype=np.float64)
        internal = ~self.trees.is_leaf
        feats = self.trees.feat[internal]
        if importance_type == "split":
            np.add.at(out, feats, 1.0)
        elif importance_type == "gain":
            np.add.at(out, feats, self.trees.split_gain[internal])
        else:
            raise ValueError(f"importance_type must be split|gain, got {importance_type}")
        return out

    # -- persistence -------------------------------------------------------------
    def save(self, path: str) -> None:
        arrays = {f"tree_{k}": v for k, v in self.trees._asdict().items()}
        arrays["thr_raw"] = self.thr_raw
        arrays["base_score"] = self.base_score
        arrays["binner_upper_bounds"] = self.binner_state["upper_bounds"]
        if self.missing_dec is not None:
            arrays["missing_dec"] = self.missing_dec
        meta = dict(
            num_class=self.num_class, objective=self.objective,
            objective_kwargs=self.objective_kwargs, depth_cap=self.depth_cap,
            best_iteration=self.best_iteration, eval_history=self.eval_history,
            binner=dict(max_bin=self.binner_state["max_bin"],
                        sample_count=self.binner_state["sample_count"],
                        seed=self.binner_state["seed"],
                        num_features=self.binner_state["num_features"],
                        categorical_features=list(
                            self.binner_state.get("categorical_features")
                            or []),
                        max_bin_by_feature=self.binner_state.get(
                            "max_bin_by_feature"),
                        feature_names=self.binner_state.get(
                            "feature_names")),
        )
        arrays["meta_json"] = np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8)
        np.savez_compressed(path, **arrays)

    @staticmethod
    def load(path: str) -> "Booster":
        if not str(path).endswith(".npz"):
            path = str(path) + ".npz"
        z = np.load(path, allow_pickle=False)
        meta = json.loads(bytes(z["meta_json"]).decode())
        trees = Tree(**_with_tree_defaults(
            {k: z[f"tree_{k}"] for k in Tree._fields if f"tree_{k}" in z}))
        binner_state = dict(meta["binner"])
        binner_state["upper_bounds"] = z["binner_upper_bounds"]
        b = Booster(
            trees, z["thr_raw"], meta["num_class"], z["base_score"],
            meta["objective"], meta["depth_cap"], binner_state,
            meta["best_iteration"], meta["eval_history"],
            meta.get("objective_kwargs") or {})
        if "missing_dec" in z:
            b.missing_dec = z["missing_dec"]
        return b

    def to_lightgbm_string(self) -> str:
        """Stock-LightGBM ``tree`` v3 text model string — loads in any
        LightGBM tooling (saveNativeModel parity, reference:
        LightGBMClassifier.scala:172-194, LightGBMBooster.scala:289)."""
        from .lgbm_format import to_lightgbm_string
        return to_lightgbm_string(self)

    @staticmethod
    def from_lightgbm_string(s: str) -> "Booster":
        """Load a LightGBM text model (produced by stock LightGBM or by
        ``to_lightgbm_string``). base_score is 0: LightGBM folds any init
        score into the first iteration's leaves."""
        from .lgbm_format import parse_lightgbm_string
        (trees, thr_raw, K, objective, kwargs, F,
         cat_features, missing_dec) = parse_lightgbm_string(s)
        M = trees.feat.shape[1]
        depth_cap = max(1, (M + 1) // 2 - 1)
        binner_state = dict(upper_bounds=np.zeros((F, 1), np.float32),
                            max_bin=0, sample_count=0, seed=0,
                            num_features=F,
                            categorical_features=list(cat_features))
        b = Booster(trees, thr_raw, K, np.zeros(K, np.float32), objective,
                    depth_cap, binner_state, objective_kwargs=kwargs)
        b.missing_dec = missing_dec
        return b

    def model_string(self) -> str:
        """Portable JSON model string (the framework's internal format:
        keeps binner state, base score and history exactly — used by
        checkpoints and pipeline persistence). For LightGBM-tool interop
        use ``to_lightgbm_string``; ``from_string`` auto-detects both."""
        d = {
            "version": 1,
            "num_class": self.num_class,
            "objective": self.objective,
            "objective_kwargs": self.objective_kwargs,
            "depth_cap": self.depth_cap,
            "best_iteration": self.best_iteration,
            "base_score": self.base_score.tolist(),
            "thr_raw": self.thr_raw.tolist(),
            "trees": {k: np.asarray(v).tolist() for k, v in self.trees._asdict().items()},
            "binner": {k: (v.tolist() if isinstance(v, np.ndarray) else v)
                       for k, v in self.binner_state.items()},
        }
        if self.missing_dec is not None:
            d["missing_dec"] = self.missing_dec.tolist()
        return json.dumps(d)

    @staticmethod
    def from_string(s: str) -> "Booster":
        if s.lstrip().startswith("tree"):
            return Booster.from_lightgbm_string(s)
        d = json.loads(s)
        trees = Tree(**_with_tree_defaults(
            {k: np.asarray(v) for k, v in d["trees"].items()}))
        binner_state = dict(d["binner"])
        binner_state["upper_bounds"] = np.asarray(
            binner_state["upper_bounds"], dtype=np.float32)
        b = Booster(trees, np.asarray(d["thr_raw"], np.float32), d["num_class"],
                    np.asarray(d["base_score"], np.float32), d["objective"],
                    d["depth_cap"], binner_state, d["best_iteration"],
                    objective_kwargs=d.get("objective_kwargs") or {})
        if d.get("missing_dec") is not None:
            b.missing_dec = np.asarray(d["missing_dec"], np.uint8)
        return b


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def _fused_es_scan(one_iter, state0, num_iterations: int,
                   early_stopping_rounds: int, higher_is_better: bool,
                   track_metric: bool, tol: float = 0.0):
    """Shared on-device training-loop harness for the fused paths (plain
    gbdt with validation, dart with/without validation).

    ``one_iter(it, state) -> (state, packed_trees [Tp] f32/i32,
    metric f32 scalar)`` — metric is ignored when ``track_metric`` is
    False. Returns ``(buf [T, Tp], mbuf [T], n_done i32, best_it i32)``;
    without metric tracking the scan runs every iteration and
    ``best_it = -1``. With it, a ``lax.while_loop`` runs every iteration
    and applies the same stopping bookkeeping the host loops use. ``tol`` is
    the improvementTolerance: an iteration only counts as improved when it
    beats the best metric by more than tol. The default 0.0 mirrors the
    host's strict compare; note a device-side tol below one f32 ulp of
    the metric value vanishes (the compare runs in f32, the host's in
    f64) — equivalence holds because the metric itself is f32-quantized,
    so any sub-ulp tolerance makes the same decision on both sides."""
    if not track_metric:
        def it_body(state, it):
            state, packed, _ = one_iter(it, state)
            return state, packed

        _, buf = lax.scan(it_body, state0,
                          jnp.arange(num_iterations, dtype=jnp.int32))
        return (buf, jnp.full((num_iterations,), jnp.nan, jnp.float32),
                jnp.int32(num_iterations), jnp.int32(-1))

    def track(best, best_it, rni, m, it):
        if higher_is_better:
            improved = m > best + jnp.float32(tol)
        else:
            improved = m < best - jnp.float32(tol)
        return (jnp.where(improved, m, best),
                jnp.where(improved, it, best_it),
                jnp.where(improved, 0, rni + 1))

    # one_iter (a whole tree's growth) is traced once, here, to a jaxpr whose
    # result shapes size the static buffers; the while body replays that
    # jaxpr, so the round is staged, lowered and compiled once and every
    # iteration, the first included, runs in the loop
    closed, shapes = jax.make_jaxpr(one_iter, return_shape=True)(
        jnp.int32(0), state0)
    out_tree = jax.tree_util.tree_structure(shapes)
    packed_s = shapes[1]

    def staged_iter(it, state):
        return jax.tree_util.tree_unflatten(out_tree, jax.core.eval_jaxpr(
            closed.jaxpr, closed.consts,
            *jax.tree_util.tree_leaves((it, state))))

    def cond(carry):
        it = carry[0]
        keep = it < num_iterations
        if early_stopping_rounds > 0:
            keep &= carry[4] < early_stopping_rounds
        return keep

    def body(carry):
        it, state, best, best_it, rni, buf, mbuf = carry
        state, packed, m = staged_iter(it, state)
        buf = lax.dynamic_update_index_in_dim(buf, packed, it, 0)
        mbuf = mbuf.at[it].set(m)
        best, best_it, rni = track(best, best_it, rni, m, it)
        return it + 1, state, best, best_it, rni, buf, mbuf

    it, _, _, best_it, _, buf, mbuf = lax.while_loop(cond, body, (
        jnp.int32(0), state0,
        jnp.float32(-jnp.inf if higher_is_better else jnp.inf),
        jnp.int32(-1), jnp.int32(0),
        jnp.zeros((num_iterations,) + packed_s.shape, packed_s.dtype),
        jnp.full((num_iterations,), jnp.nan, jnp.float32)))
    return buf, mbuf, it, best_it


def _grow_with_warmup(grow, it_scalar, cfg, qk, binned_t, grad_k, hess_k,
                      row_mask, fmask, *, axis_name, is_cat):
    """Dispatch one tree growth honoring ``quant_warmup_iters``: iterations
    below the warmup count grow at full precision, later ones ride the int8
    quantized-histogram path (GrowConfig.quant_warmup_iters rationale). Both
    variants live in ONE ``lax.cond`` so the fused scans and the
    early-stopping while_loop keep their traced iteration index; the
    predicate derives from the replicated scan counter, so the branch cannot
    diverge across shards. Returns ``(tree, row_node, tally)``: the tree's
    run tally, the variants' tallies (:func:`_grow_variants`,
    :func:`_tally_words`) end to end and zero where a variant did not run."""
    variants, words = _grow_variants(cfg), _tally_words(cfg)

    def grown(i):
        def run():
            got = []
            tree, row_node = grow(
                binned_t, grad_k, hess_k, row_mask, fmask, variants[i],
                axis_name=axis_name, is_cat=is_cat,
                qkey=qk if variants[i].quantized_grad else None,
                run_tally=got)
            return tree, row_node, jnp.concatenate([
                got[0] if j == i else jnp.zeros(w, jnp.int32)
                for j, w in enumerate(words)])
        return run

    if len(variants) == 1:
        return grown(0)()
    return lax.cond(it_scalar < cfg.quant_warmup_iters, grown(0), grown(1))


def _note_valid_evals(metric: str, where: str, evals: int, rows: int) -> None:
    """``gbdt_valid_metric_total{metric, where=device|host}``: evaluations of
    the validation metric that a fit's history records, by where the round
    loop read them (the host loop one a round on the host, the fused path all
    ``n_done`` in the device's ``while_loop``), and ``gbdt_valid_rows_total``,
    the held-out rows scored for them."""
    _metrics.safe_counter("gbdt_valid_metric_total", metric=metric,
                          where=where).inc(evals)
    _metrics.safe_counter("gbdt_valid_rows_total").inc(evals * rows)


def _combine_metric(local, local_wsum, metric_name: str):
    """One shard's ``eval_metric`` value to the whole set's (inside
    ``shard_map`` over ``data``): a weighted mean is combined by weight,
    ``rmse`` through its square; a metric that ``eval_metric`` already took
    over every shard's rows (``GLOBAL_EVAL_METRICS``) is passed through."""
    if metric_name in GLOBAL_EVAL_METRICS:
        return local
    wsum = jax.lax.psum(local_wsum, "data")
    if metric_name == "rmse":
        return jnp.sqrt(jax.lax.psum(local * local * local_wsum, "data")
                        / wsum)
    return jax.lax.psum(local * local_wsum, "data") / wsum


def _grow_axis_for(mesh, cfg) -> "str | None":
    """Collective axis for tree growth: None on a single-shard data axis,
    where a psum would be the identity. Voting keeps the axis
    even at size 1: its top-2k ballot restricts the split search and must
    behave identically regardless of shard count — and so does a resolved
    hist_blocks (the deterministic blocked reduction must run the SAME
    gather-fold program on a 1-device mesh that it runs on 8)."""
    det = isinstance(cfg.hist_blocks, int) and cfg.hist_blocks > 1
    return ("data" if (dict(mesh.shape).get("data", 1) > 1 or cfg.voting
                       or det)
            else None)


@_in_fit_span
def train_booster(
    X: Optional[np.ndarray] = None,
    y: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
    *,
    dataset: Optional[LightGBMDataset] = None,
    objective: str = "regression",
    num_class: int = 1,
    num_iterations: int = 100,
    cfg: Optional[GrowConfig] = None,
    max_bin: int = 255,
    bin_sample_count: int = 200_000,
    feature_fraction: float = 1.0,
    bagging_fraction: float = 1.0,
    bagging_freq: int = 0,
    seed: int = 0,
    valid_set: "Optional[LightGBMDataset | Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]" = None,
    early_stopping_rounds: int = 0,
    init_booster: Optional[Booster] = None,
    boost_from_average: bool = True,
    mesh: Optional[Mesh] = None,
    objective_kwargs: Optional[dict] = None,
    iteration_callback: Optional[Callable[[int, Dict[str, float]], None]] = None,
    metric_eval_period: int = 1,
    row_valid: Optional[np.ndarray] = None,
    boosting_type: str = "gbdt",
    top_rate: float = 0.2,
    other_rate: float = 0.1,
    drop_rate: float = 0.1,
    max_drop: int = 50,
    skip_drop: float = 0.5,
    drop_seed: int = 4,
    checkpoint_dir: Optional[str] = None,
    checkpoint_period: int = 10,
    categorical_features=(),
    bin_dtype=None,
    pos_bagging_fraction: float = 1.0,
    neg_bagging_fraction: float = 1.0,
    early_stopping_tolerance: float = 0.0,
    provide_training_metric: bool = False,
    max_bin_by_feature=None,
    eval_metric_name: Optional[str] = None,
    _phases: Optional[_Phases] = None,    # _in_fit_span's, never a caller's
) -> Booster:
    """Train a boosted ensemble, rows sharded over the mesh ``data`` axis.

    The per-iteration schedule matches the reference's trainCore
    (TrainUtils.scala:220-315): update one iteration (K trees for K classes),
    evaluate on the optional validation set, maybe early-stop;
    ``iteration_callback`` is the delegate hook
    (reference: lightgbm/LightGBMDelegate.scala).

    ``dataset`` (a pre-built :class:`LightGBMDataset`) skips the per-call
    ingest — binner fit, feature transfer, device binning — the way the
    reference trains against a pre-constructed native dataset
    (lightgbm/LightGBMDataset.scala). When given, ``X``/``y``/``weight``/
    ``max_bin``/``bin_sample_count``/``categorical_features``/``row_valid``/
    ``mesh`` are taken from the dataset (``X`` may still be passed alongside
    for ``init_booster`` warm starts, which score raw rows).

    ``valid_set`` is the held-out rows: a :class:`LightGBMDataset` built once
    with ``construct(..., reference=<the training dataset>)`` (binned by the
    training set's binner, resident, sharded like the training rows: what
    LightGBM's ``Dataset(reference=train)`` is), or ``(X, y, weight)`` host
    arrays, from which one is built for this fit. After every iteration the
    new trees score them and the metric (``eval_metric_name``, else the
    objective's default) is taken on the device, ``auc`` included; without
    callbacks, checkpoints or a longer ``metric_eval_period`` the whole
    early-stopped fit is one dispatch (``path`` ``fused_valid``).
    """
    phases, fit = _phases, _phases.parent       # the gbdt_fit span's own
    phases.enter("gbdt_fit_prepare")
    # persistent compile cache (utils/compile_cache): wire it before the
    # first program of this fit traces, so serving workers and repeat CLI
    # fits skip the cold multi-second XLA compile
    _compile_cache.ensure()
    # each fit starts with clean training-health sentinel windows — a
    # diverging fit yesterday must not poison today's gauge
    _watchdog.reset_training_health("gbdt")
    cfg = cfg or GrowConfig()
    if dataset is not None and checkpoint_dir is not None:
        raise ValueError(
            "checkpointDir requires raw X/y arrays (the resume fingerprint "
            "hashes them); pass arrays instead of a pre-built dataset")
    if dataset is None and (X is None or y is None):
        raise ValueError("either X and y arrays or dataset= must be given")
    if dataset is not None and (y is not None or weight is not None
                                or row_valid is not None):
        # X alone is allowed alongside dataset= (init_booster warm starts
        # score raw rows); anything else would be silently ignored in favor
        # of the dataset's stored arrays — refuse instead
        raise ValueError(
            "y/weight/row_valid are baked into the dataset at construct() "
            "time; do not pass them alongside dataset=")
    # --- step-level checkpoint resume (SURVEY.md §5): the newest checkpoint
    # becomes the warm-start booster and already-completed iterations are
    # skipped; the caller's init_booster is subsumed (training that produced
    # the checkpoint already started from it). Checkpoints carry a
    # data+config fingerprint — a stale checkpoint from different data or
    # hyperparameters is ignored, not silently resumed.
    if boosting_type not in ("gbdt", "goss", "rf", "dart"):
        raise ValueError(
            f"boostingType {boosting_type!r} is not supported "
            "(supported: gbdt, rf, dart, goss)")
    if boosting_type in ("rf", "dart"):
        if init_booster is not None:
            raise ValueError(
                f"warm start (modelString/numBatches) is not supported with "
                f"boostingType={boosting_type!r}: its trees carry "
                "normalization state that a warm-start prefix lacks")
        if checkpoint_dir is not None:
            raise ValueError(
                f"checkpointDir is not supported with "
                f"boostingType={boosting_type!r} (gbdt/goss only)")
    stratified_bagging = (pos_bagging_fraction != 1.0
                          or neg_bagging_fraction != 1.0)
    if boosting_type == "rf" and not (
            (bagging_fraction < 1.0 or stratified_bagging)
            and bagging_freq > 0):
        raise ValueError(
            "boostingType='rf' requires bagging: set baggingFraction < 1.0 "
            "(or pos/negBaggingFraction) and baggingFreq > 0 (LightGBM "
            "semantics — without bagging every random-forest tree would be "
            "identical)")
    if stratified_bagging:
        # LightGBM: pos/neg bagging fractions are a binary-only, set-together
        # stratified alternative to bagging_fraction
        if objective != "binary":
            raise ValueError(
                "posBaggingFraction/negBaggingFraction apply to the binary "
                f"objective only (got objective={objective!r})")
        if bagging_freq <= 0:
            raise ValueError(
                "posBaggingFraction/negBaggingFraction need baggingFreq > 0")
        if not (0.0 < pos_bagging_fraction <= 1.0
                and 0.0 < neg_bagging_fraction <= 1.0):
            raise ValueError(
                "pos/negBaggingFraction must be in (0, 1]; got "
                f"{pos_bagging_fraction}/{neg_bagging_fraction}")
        if boosting_type == "goss":
            raise ValueError("goss does its own gradient-based sampling; "
                             "pos/negBaggingFraction do not apply")
        if boosting_type == "dart":
            raise ValueError(
                "pos/negBaggingFraction are supported for gbdt/rf; dart's "
                "fused drop-schedule path keeps plain baggingFraction")
        # LightGBM semantics: when the stratified fractions are set they
        # replace bagging_fraction entirely — reject the ambiguous combo
        # rather than silently ignoring one of them
        if bagging_fraction < 1.0:
            raise ValueError(
                "set either baggingFraction or pos/negBaggingFraction, "
                "not both (the stratified fractions replace it)")
    if early_stopping_tolerance < 0:
        raise ValueError(
            f"improvementTolerance must be >= 0, got {early_stopping_tolerance}")
    if provide_training_metric and boosting_type in ("rf", "dart"):
        raise ValueError(
            "isProvideTrainingMetric is supported for gbdt/goss (rf keeps "
            "train scores at the base margin and dart rescales past trees "
            "each iteration, so neither has a running train margin to "
            "evaluate)")
    # metric override (LightGBM `metric` param): validated against the
    # objective family before anything traces
    requested_metric = (eval_metric_name or "").strip() or None
    eval_override = requested_metric
    if eval_override:
        from .objectives import SUPPORTED_EVAL_METRICS
        fam = objective if objective in ("binary", "multiclass",
                                         "lambdarank") else "_regression"
        allowed = SUPPORTED_EVAL_METRICS[fam]
        if eval_override not in allowed:
            raise ValueError(
                f"metric={eval_override!r} is not supported for the "
                f"{objective!r} objective (choose from {allowed})")
        if boosting_type == "dart":
            raise ValueError("metric overrides are not supported with "
                             "dart (its fused drop-schedule eval keeps the "
                             "objective default)")

    ckpt_mgr = None
    ckpt_fingerprint = None
    iterations_done = 0
    user_init_booster = init_booster
    resume_state: Optional[dict] = None
    if checkpoint_dir is not None:
        from ...utils.checkpoint import CheckpointManager, data_fingerprint
        cfg_norm = cfg._replace(num_bins=max_bin)
        ckpt_fingerprint = data_fingerprint(
            np.asarray(X, np.float32), np.asarray(y, np.float32),
            None if weight is None else np.asarray(weight, np.float32),
            # the warm-start model is part of run identity: resuming a
            # checkpoint that subsumed a *different* init would be silent.
            # Every param that shapes the trained model belongs here —
            # bin_sample_count/boost_from_average change bin boundaries /
            # the base score, so a changed value must invalidate resume.
            config=(objective, num_class, cfg_norm, max_bin, bin_sample_count,
                    tuple(int(i) for i in categorical_features),
                    boost_from_average, feature_fraction,
                    bagging_fraction, bagging_freq, seed, boosting_type,
                    top_rate, other_rate,
                    pos_bagging_fraction, neg_bagging_fraction,
                    early_stopping_tolerance,
                    requested_metric,
                    None if max_bin_by_feature is None
                    else tuple(int(b) for b in max_bin_by_feature),
                    sorted((objective_kwargs or {}).items()),
                    None if user_init_booster is None
                    else user_init_booster.model_string()))
        # namespaced by fingerprint: concurrent runs sharing checkpoint_dir
        # (sweeps) never purge each other's files
        ckpt_mgr = CheckpointManager(checkpoint_dir,
                                     namespace=ckpt_fingerprint[:12])
        # resolved here, before any compiled-program cache key is built
        # (the resolve-before-cache-key rule): the dump hook itself is
        # armed much later, next to the round loop
        dump_on_unhealthy = os.environ.get(
            "MMLSPARK_TPU_CHECKPOINT_ON_UNHEALTHY",
            "").lower() in ("1", "true", "yes")
        latest = ckpt_mgr.latest_matching(ckpt_fingerprint)
        # MMLSPARK_TPU_STRICT_RESUME=1: resume-or-die — checkpoints that
        # exist but mismatch (changed data/config/warm start) raise a
        # CheckpointMismatchError instead of silently retraining from
        # scratch. Only probed when the namespaced resume found NOTHING
        # (the happy path must not unpickle every file twice), and the
        # probe checks ACROSS namespaces: the un-namespaced inspection
        # view sees the mismatched files a namespaced manager filters
        # out (config drift changes the namespace).
        if latest is None and os.environ.get(
                "MMLSPARK_TPU_STRICT_RESUME",
                "").lower() in ("1", "true", "yes"):
            # a MATCH here is a legacy un-namespaced checkpoint the
            # namespaced manager can't see — resume from it rather than
            # silently retraining (the outcome strict mode forbids)
            latest = CheckpointManager(checkpoint_dir).latest_matching(
                ckpt_fingerprint, purge_stale=False, strict=True)
        if latest is not None:
            step, payload = latest
            init_booster = Booster.from_string(payload["model"])
            iterations_done = payload["iteration"] + 1
            resume_state = payload
            if iterations_done >= num_iterations:
                # checkpoint already covers the request: truncate to the
                # warm-start prefix plus the requested trained iterations
                prior = payload.get("prior_iterations", 0)
                return _truncate_booster(init_booster,
                                         prior + num_iterations)

    if boosting_type == "rf":
        # random forest: no shrinkage; the averaged ensemble is scaled at
        # finalize time instead (LightGBM rf semantics)
        cfg = cfg._replace(learning_rate=1.0)
    objective_kwargs = objective_kwargs or {}
    obj = get_objective(objective, num_class, **objective_kwargs)
    K = obj.num_scores

    if dataset is None:
        dataset = LightGBMDataset.construct(
            _densify(X), y, weight, max_bin=max_bin,
            bin_sample_count=bin_sample_count, seed=seed,
            categorical_features=categorical_features, mesh=mesh,
            row_valid=row_valid, bin_dtype=bin_dtype,
            max_bin_by_feature=max_bin_by_feature)
    mesh = dataset.mesh
    binner = dataset.binner
    max_bin = dataset.max_bin
    cfg = cfg._replace(num_bins=max_bin)
    n, n_pad, F = dataset.n, dataset.n_pad, dataset.num_features
    fit.set(trees=num_iterations * K, rows=n, features=F,
            stats="int8" if cfg.quantized_grad else "bf16")
    Xbt_d, y_d, w_d, vmask_d = (dataset.Xbt_d, dataset.y_d, dataset.w_d,
                                dataset.vmask_d)
    # categorical routing mask: None when absent so the purely-numeric path
    # compiles with zero bitset overhead
    is_cat_np = binner.is_cat_mask()
    is_cat_j = jnp.asarray(is_cat_np) if is_cat_np.any() else None
    nshards = meshlib.num_shards(mesh)
    fit.set(shards=nshards)

    # placement + determinism resolution — BEFORE any compiled-program
    # cache key below (the PR 4 resolve-before-cache-key rule): the plan
    # resolves the backend (which decides buffer donation) and emits the
    # placement flight event; hist_blocks resolves the canonical reduction
    # geometry. Both land in cfg / the cache key as concrete values.
    plan = placement.plan_for("gbdt.fit", mesh=mesh, rows=n_pad,
                              boosting=boosting_type)
    cfg = cfg._replace(hist_blocks=placement.resolve_hist_blocks(
        cfg.hist_blocks, mesh, n_pad, voting=cfg.voting))
    deterministic = isinstance(cfg.hist_blocks, int) and cfg.hist_blocks > 1

    # base score (replicated scalar per class). Computed on device from the
    # already-sharded label/weight arrays, then broadcast to the initial
    # score matrix on device — no dataset-sized host round-trips.
    if init_booster is not None:
        base = init_booster.base_score
        if X is None:
            raise ValueError(
                "init_booster warm start scores raw rows: pass X alongside "
                "dataset=")
        # checkpoint resume restores the EXACT accumulated score matrix
        # the interrupted run held (downloaded into the payload at save
        # time): re-deriving it via predict_raw would replay the forest
        # in a different float-summation order and the resumed run would
        # drift from the uninterrupted one by an ulp — enough to pick
        # different splits. Stored state is what makes a failpoint-killed
        # fit resume to bit-identical trees. Shape-guarded fallback:
        # an old-format checkpoint re-scores through the model.
        resume_scores = (None if resume_state is None
                         else resume_state.get("scores"))
        if resume_scores is not None and \
                np.asarray(resume_scores).shape == (n, K):
            scores0 = np.asarray(resume_scores, np.float32)
        else:
            scores0 = init_booster.predict_raw(
                np.asarray(_densify(X), np.float32))  # [n, K]
        scores_d, _ = placement.shard_rows(scores0.astype(np.float32), mesh)
    elif boost_from_average:
        if deterministic:
            # topology-independent base score: a jit reduction over sharded
            # arrays lets GSPMD pick a device-count-dependent f32 combine
            # order, so the deterministic mode gathers the (one-time,
            # [n]-sized) label/weight arrays and computes the init score on
            # the default device — the same program at every device count.
            base_d = jnp.broadcast_to(
                obj.init_score(
                    placement.to_device(placement.to_host(y_d)),
                    placement.to_device(placement.to_host(w_d)
                                        * placement.to_host(vmask_d))),
                (K,)).astype(jnp.float32)
        else:
            base_fn = _cached_program(
                ("init_score", objective, num_class,
                 tuple(sorted(objective_kwargs.items())), y_d.shape, mesh),
                lambda: jax.jit(lambda yy, ww, vm: jnp.broadcast_to(
                    obj.init_score(yy, ww * vm), (K,)).astype(jnp.float32)))
            base_d = base_fn(y_d, w_d, vmask_d)
        base = np.asarray(base_d, dtype=np.float32)
        scores_d = _device_tile_scores(base_d, n_pad, K, mesh)
    else:
        base = np.zeros(K, dtype=np.float32)
        scores_d = _device_tile_scores(jnp.zeros(K, jnp.float32), n_pad, K,
                                       mesh)

    # the validation set is a dataset binned once by the training set's
    # binner (LightGBM's ``Dataset(reference=train)``); raw arrays are the
    # thin case that builds one here
    has_valid = valid_set is not None
    valid_fp = None
    nv = 0
    if has_valid:
        Xv = None
        if isinstance(valid_set, LightGBMDataset):
            valid_ds = valid_set
            if valid_ds.binner is not binner or valid_ds.mesh is not mesh:
                raise ValueError(
                    "a pre-built validation dataset has to be constructed "
                    "with reference=<the training dataset>: it shares its "
                    "binner and its mesh")
            if init_booster is not None:
                raise ValueError(
                    "init_booster warm start scores raw validation rows: "
                    "pass valid_set=(X, y, w) arrays")
        else:
            Xv, yv, wv = valid_set
            Xv = np.asarray(_densify(Xv), np.float32)
            yv = np.asarray(yv, np.float32)
            if ckpt_mgr is not None:
                # the valid set is NOT part of the resume fingerprint (a
                # changed eval set must not discard training progress), so
                # the exact-state vscores restore needs its own identity
                # check — restoring V1's accumulated scores against V2's
                # labels would silently corrupt early stopping
                from ...utils.checkpoint import data_fingerprint as _vfp
                valid_fp = _vfp(Xv, yv, np.ones_like(yv) if wv is None
                                else np.asarray(wv, np.float32))
            valid_ds = LightGBMDataset.construct(Xv, yv, wv,
                                                 reference=dataset)
        nv, nv_pad = valid_ds.n, valid_ds.n_pad
        Xvb_d, yv_d = valid_ds.Xbt_d, valid_ds.y_d
        # validity folded into the weight so padded rows don't count
        wv_d = valid_ds.eval_weight()
        # same exact-state rule as the training scores above — but only
        # when the checkpoint was written against THIS valid set
        resume_vscores = (None if resume_state is None
                          else resume_state.get("vscores"))
        if (resume_vscores is not None and valid_fp is not None
                and resume_state.get("valid_fingerprint") == valid_fp
                and np.asarray(resume_vscores).shape == (nv, K)):
            vscores_d, _ = placement.shard_rows(
                np.asarray(resume_vscores, np.float32), mesh)
        elif init_booster is not None:
            vscores_d, _ = placement.shard_rows(
                init_booster.predict_raw(Xv).astype(np.float32), mesh)
        else:
            vscores_d = _device_tile_scores(jnp.asarray(base), nv_pad, K,
                                            mesh)
    else:
        Xvb_d = yv_d = wv_d = vscores_d = None

    depth_cap = cfg.max_depth if cfg.max_depth > 0 else max(1, cfg.num_leaves - 1)
    depth_cap = min(depth_cap, 2 * cfg.num_leaves)

    use_goss = boosting_type == "goss"
    is_rf = boosting_type == "rf"
    use_bagging = ((not use_goss) and bagging_freq > 0
                   and (bagging_fraction < 1.0 or stratified_bagging))
    # the metric's name as the history records it: an override's own, else
    # the objective's default (a one-row probe names it)
    metric_name = (eval_override if eval_override in GLOBAL_EVAL_METRICS
                   else eval_metric(
                       obj, jnp.zeros((1, K)) if K > 1 else jnp.zeros(1),
                       jnp.zeros(1), jnp.ones(1), metric=eval_override,
                       **objective_kwargs)[0])
    # a rank statistic is taken over every shard's rows at once
    metric_axis = "data" if nshards > 1 else None
    if has_valid:
        fit.set(valid_rows=nv, metric=metric_name)

    if boosting_type == "dart":
        return _train_dart(
            mesh=mesh, cfg=cfg, K=K, obj=obj,
            objective=objective, objective_kwargs=objective_kwargs,
            Xbt_d=Xbt_d, y_d=y_d, w_d=w_d, vmask_d=vmask_d, base=base,
            has_valid=has_valid, Xvb_d=Xvb_d, yv_d=yv_d, wv_d=wv_d,
            depth_cap=depth_cap, metric_name=metric_name,
            num_iterations=num_iterations, seed=seed,
            feature_fraction=feature_fraction, use_bagging=use_bagging,
            bagging_fraction=bagging_fraction, bagging_freq=bagging_freq,
            early_stopping_rounds=early_stopping_rounds,
            early_stopping_tolerance=float(early_stopping_tolerance),
            iteration_callback=iteration_callback,
            metric_eval_period=metric_eval_period,
            drop_rate=drop_rate, max_drop=max_drop, skip_drop=skip_drop,
            drop_seed=drop_seed, binner=binner, max_bin=max_bin,
            is_cat_j=is_cat_j, phases=phases)

    grow_axis = _grow_axis_for(mesh, cfg)

    def step_local(binned_t, yl, wl, vmask, scores, vbinned, vy, vw,
                   vscores, key, bag_key, it_f):
        """One boosting iteration on local shard rows (inside shard_map).

        ``it_f``: f32 iteration index — gates the quantized-gradient warmup
        cond (``_grow_with_warmup``), and rf's validation metric evaluates
        the *average* of the trees grown so far.
        """
        with jax.named_scope("gbdt_grad"):
            if K > 1:
                grad, hess = obj.grad_hess(scores, yl, wl)
            else:
                grad, hess = obj.grad_hess(scores[:, 0], yl, wl)
                grad, hess = grad[:, None], hess[:, None]
            if use_goss:
                # GOSS (boostingType=goss): keep the top_rate fraction by
                # |grad|, sample other_rate of the rest amplified by
                # (1-a)/b. The amplification rides the row mask, so weighted
                # counts see it too (a documented deviation from LightGBM's
                # unweighted counts).
                absg = jnp.abs(grad).sum(axis=1) * vmask
                n_valid = jnp.maximum(jnp.sum(vmask), 1.0)
                # keep top_rate*n_valid rows of an N-row shard (padded rows
                # have absg 0 and cluster at the bottom of the quantile)
                q = jnp.clip(1.0 - top_rate * n_valid / vmask.shape[0],
                             0.0, 1.0)
                top = absg >= jnp.quantile(absg, q)
                k2 = jax.random.fold_in(bag_key, jax.lax.axis_index("data"))
                keep_p = other_rate / max(1.0 - top_rate, 1e-6)
                rest_keep = jax.random.uniform(k2, vmask.shape) < keep_p
                amp = (1.0 - top_rate) / max(other_rate, 1e-6)
                row_mask = vmask * jnp.where(top, 1.0,
                                             jnp.where(rest_keep, amp, 0.0))
            elif use_bagging:
                # bag_key changes only every bagging_freq iterations (LightGBM
                # semantics: the subsample is reused for baggingFreq rounds)
                k = jax.random.fold_in(bag_key, jax.lax.axis_index("data"))
                if stratified_bagging:
                    # LightGBM pos/neg_bagging_fraction: per-class keep
                    # probability (binary labels; validated at entry)
                    frac = jnp.where(yl > 0.5,
                                     jnp.float32(pos_bagging_fraction),
                                     jnp.float32(neg_bagging_fraction))
                else:
                    frac = jnp.float32(bagging_fraction)
                bag = (jax.random.uniform(k, vmask.shape) < frac)
                row_mask = vmask * bag.astype(jnp.float32)
            else:
                row_mask = vmask

        trees_out, tallies = [], []
        fmask = jnp.ones(F, dtype=bool)
        if feature_fraction < 1.0:
            # derived from the replicated iteration key: identical on all shards
            fkey = jax.random.fold_in(key, 7)
            u = jax.random.uniform(fkey, (F,))
            fmask = u < feature_fraction
            fmask = fmask.at[jnp.argmin(u)].set(True)  # guarantee >=1 feature
        grow = (grow_tree_depthwise if cfg.growth_policy == "depthwise"
                else grow_tree)
        for k in range(K):
            tree, row_node, tally = _grow_with_warmup(
                grow, it_f, cfg, jax.random.fold_in(key, 13 + k),
                binned_t, grad[:, k], hess[:, k], row_mask, fmask,
                axis_name=grow_axis, is_cat=is_cat_j)
            tallies.append(tally)
            if not is_rf:
                # rf: trees are independent (gradients stay at the base
                # score); gbdt/goss: boost on the updated margin
                with jax.named_scope("gbdt_score_update"):
                    scores = scores.at[:, k].add(tree.leaf_value[row_node])
            trees_out.append(tree)
        trees_stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *trees_out)

        metrics = {}
        if provide_training_metric:
            # isProvideTrainingMetric: the train-set metric on the updated
            # margin, combined across shards exactly like the valid metric
            tsc = scores if K > 1 else scores[:, 0]
            _, tnum = eval_metric(obj, tsc, yl, wl * vmask,
                                  metric=eval_override, axis_name=metric_axis,
                                  **objective_kwargs)
            metrics["train"] = _combine_metric(tnum, jnp.sum(wl * vmask),
                                               metric_name)
        if has_valid:
            for k in range(K):
                tr = jax.tree_util.tree_map(lambda a: a[k], trees_stacked)
                vscores = vscores.at[:, k].add(
                    predict_tree_binned(tr, vbinned, is_cat=is_cat_j))
            if is_rf:
                # ensemble-so-far = base + average of accumulated raw trees
                vbase = jnp.asarray(base)[None, :]
                veval = vbase + (vscores - vbase) / (it_f + 1.0)
            else:
                veval = vscores
            sc = veval if K > 1 else veval[:, 0]
            _, num = eval_metric(obj, sc, vy, vw, metric=eval_override,
                                 axis_name=metric_axis, **objective_kwargs)
            metrics["valid"] = _combine_metric(num, jnp.sum(vw), metric_name)
        return (scores, vscores if has_valid else jnp.zeros((1, 1)),
                trees_stacked, metrics, jnp.stack(tallies))

    row_spec = P("data")
    row2_spec = P("data", None)
    col_spec = P(None, "data")
    in_specs = (col_spec, row_spec, row_spec, row_spec, row2_spec,
                col_spec if has_valid else P(), row_spec if has_valid else P(),
                row_spec if has_valid else P(), row2_spec if has_valid else P(),
                P(), P(), P())
    out_specs = (row2_spec, row2_spec if has_valid else P(), P(), P())

    dummy = np.zeros((), np.float32)
    # cache the compiled step across train_booster calls: the closure is fresh
    # per call, so jit's identity-keyed cache would otherwise recompile.
    # The resolved histogram engine keys the cache too: engine selection is
    # trace-time static (env/backend), so an MMLSPARK_TPU_HIST_ENGINE flip
    # mid-process must build a new program, not reuse the old engine's.
    from ...ops.histogram import resolve_engine as _resolve_hist_engine
    cache_key = (_resolve_hist_engine(),
                 cfg, K, objective, tuple(sorted(objective_kwargs.items())),
                 tuple(np.flatnonzero(is_cat_np).tolist()),
                 Xbt_d.shape, None if not has_valid else Xvb_d.shape,
                 use_bagging, bagging_fraction, bagging_freq,
                 stratified_bagging, pos_bagging_fraction,
                 neg_bagging_fraction, provide_training_metric,
                 eval_override, feature_fraction, depth_cap,
                 boosting_type, top_rate, other_rate, mesh,
                 # rf's validation eval closes over the data-dependent base
                 # score; it must key the cache or a sweep over same-shape
                 # datasets would reuse the wrong base
                 tuple(np.asarray(base).tolist()) if is_rf else None)
    def step_packed(*args):
        scores, vscores, trees_stacked, metrics, tally = step_local(*args)
        # one flat download buffer instead of 13 per-field transfers
        return scores, vscores, pack_trees(trees_stacked, tally), metrics

    # donate the per-round score buffers: the host loop immediately rebinds
    # scores_d/vscores_d to the step outputs, so XLA can update them in
    # place instead of allocating + copying a fresh [n_pad, K] in HBM every
    # boosting round. vscores (arg 8) only when real — without validation
    # that slot holds a shared dummy scalar whose shape matches no output,
    # and donating it would just warn per call. ACCELERATORS ONLY: on the
    # XLA CPU backend donating these sharded shard_map buffers produced
    # nondeterministic heap corruption (review-reproduced: ~40% of
    # test_histogram_engines runs segfaulted mid-host-loop on jax 0.4.37;
    # 0/6 with donation off), and host-RAM copies are not the bottleneck
    # the donation targets anyway. The placement plan resolved the backend
    # up front (PlacementPlan.donate_buffers).
    if not plan.donate_buffers:
        donate = ()
    else:
        donate = (4, 8) if has_valid else (4,)
    fit.set(path="host_loop", program=_hit_or_built(cache_key))
    step = _cached_program(cache_key, lambda: jax.jit(shard_map(
        step_packed, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False), donate_argnums=donate))

    all_trees: List[Tree] = []
    tallies: List[np.ndarray] = []    # the run tally of every download
    history: Dict[str, List[float]] = {metric_name: []}
    higher_is_better = metric_name in HIGHER_IS_BETTER
    es_tol = float(early_stopping_tolerance)
    best_metric = -np.inf if higher_is_better else np.inf
    best_iter, rounds_no_improve = -1, 0
    if resume_state is not None:
        # continue the early-stopping bookkeeping exactly where it stopped
        best_metric = resume_state.get("best_metric", best_metric)
        best_iter = resume_state.get("best_iter", best_iter)
        rounds_no_improve = resume_state.get("rounds_no_improve", 0)
        history = resume_state.get("history", history)

    def _iter_keys(base_key, it):
        """Per-iteration PRNG derivation, shared by the host loop and both
        fused paths — host/fused equivalence depends on these staying
        bit-identical (``it`` may be a Python int or a traced scalar)."""
        key = jax.random.fold_in(base_key, it)
        if use_goss or is_rf:
            # GOSS resamples every iteration; rf re-bags every iteration
            # too (its gradients are constant, so a reused bag would
            # duplicate trees); gbdt bagging reuses its subsample for
            # bagging_freq rounds (LightGBM semantics)
            bag_step = it
        elif use_bagging:
            bag_step = it // max(bagging_freq, 1)
        else:
            bag_step = 0
        return key, jax.random.fold_in(base_key, 1_000_003 + bag_step)

    # --- fused fast path: no validation loop, no delegate callbacks, no
    # checkpointing, no resume -> run every iteration inside ONE compiled
    # scan. One device dispatch instead of num_iterations round-trips, which
    # dominates wall time on remote-attached TPUs.
    fuse = (not has_valid and iteration_callback is None and ckpt_mgr is None
            and iterations_done == 0 and not provide_training_metric)
    if fuse:
        fuse_key = (cache_key, num_iterations, seed, "fused")

        def build_multi():
            def multi_local(binned_l, yl, wl, vmask_l, scores_l):
                base_key = jax.random.PRNGKey(seed)

                def it_body(scores_c, it):
                    key, bag_key = _iter_keys(base_key, it)
                    d = jnp.zeros((), jnp.float32)
                    scores_c, _, trees_stacked, _, tally = step_local(
                        binned_l, yl, wl, vmask_l, scores_c, d, d, d, d,
                        key, bag_key, it.astype(jnp.float32))
                    return scores_c, (trees_stacked, tally)

                _, (trees_seq, tallies) = lax.scan(
                    it_body, scores_l,
                    jnp.arange(num_iterations, dtype=jnp.int32))
                # one flat download buffer instead of 13 per-field transfers
                return pack_trees(trees_seq, tallies)

            return jax.jit(shard_map(
                multi_local, mesh=mesh,
                in_specs=(col_spec, row_spec, row_spec, row_spec, row2_spec),
                out_specs=P(), check_vma=False))

        multi = phases.program("fused", fuse_key, build_multi)
        # a first call traces, lowers and compiles (or loads) in here:
        # utils/compile_cache.py records those stages as children
        phases.enter("gbdt_fit_dispatch")
        trees_dev = multi(Xbt_d, y_d, w_d, vmask_d, scores_d)
        phases.enter("gbdt_fit_wait")
        jax.block_until_ready(trees_dev)
        phases.enter("gbdt_fit_download")
        trees_seq, tallies = _unpack_fit(np.asarray(trees_dev),
                                         (num_iterations, K), cfg)
        phases.enter("gbdt_fit_finalize")
        _tell_run_tally(fit, cfg, nshards, K, [tallies])
        all_seq: List[Tree] = []
        for it in range(num_iterations):
            for k in range(K):
                all_seq.append(jax.tree_util.tree_map(
                    lambda a: a[it, k], trees_seq))
        booster = _finalize_trees(all_seq, binner, max_bin, K, base, objective,
                                  depth_cap, objective_kwargs, -1,
                                  {metric_name: []}, init_booster)
        if is_rf:
            booster = _scale_booster_values(
                booster, np.full(booster.num_trees,
                                 1.0 / booster.num_iterations))
        return booster

    def _finalize(trees_list: List[Tree]) -> Booster:
        return _finalize_trees(trees_list, binner, max_bin, K, base,
                               objective, depth_cap, objective_kwargs,
                               best_iter, history, init_booster)

    # --- fused early-stopped validation path: validation + early-stopping
    # bookkeeping run ON DEVICE inside one lax.while_loop, so an
    # early-stopped training run is still ONE dispatch (the host loop costs
    # a device round-trip per iteration). The stopping
    # predicate derives from the psum'd metric — replicated across shards,
    # so the while cond is SPMD-safe. Gated to the plain configuration
    # (period-1 eval, no callbacks/checkpoint/resume) and equivalence with
    # the host loop is pinned by tests (same best_iter, history, model);
    # MMLSPARK_TPU_DISABLE_FUSED_VALID=1 forces the host loop.
    fuse_es = (has_valid and iteration_callback is None and ckpt_mgr is None
               and iterations_done == 0 and metric_eval_period == 1
               and not provide_training_metric
               and not os.environ.get("MMLSPARK_TPU_DISABLE_FUSED_VALID"))  # graftlint: disable=resolve-before-cache-key (gates the fused path off entirely; never feeds a key)
    if fuse_es:
        fuse_key = (cache_key, num_iterations, seed, early_stopping_rounds,
                    es_tol, "fused_valid")

        def build_multi_valid():
            def multi_local(binned_l, yl, wl, vmask_l, scores_l, vbinned_l,
                            vy_l, vw_l, vscores_l):
                base_key = jax.random.PRNGKey(seed)

                def one_iter(it, state):
                    scores_c, vscores_c = state
                    key, bag_key = _iter_keys(base_key, it)
                    (scores_c, vscores_c, trees_stacked, metrics,
                     tally) = step_local(
                        binned_l, yl, wl, vmask_l, scores_c, vbinned_l,
                        vy_l, vw_l, vscores_c, key, bag_key,
                        it.astype(jnp.float32))
                    return ((scores_c, vscores_c),
                            pack_trees(trees_stacked, tally),
                            metrics["valid"].astype(jnp.float32))

                return _fused_es_scan(one_iter, (scores_l, vscores_l),
                                      num_iterations, early_stopping_rounds,
                                      higher_is_better, True, tol=es_tol)

            return jax.jit(shard_map(
                multi_local, mesh=mesh,
                in_specs=(col_spec, row_spec, row_spec, row_spec, row2_spec,
                          col_spec, row_spec, row_spec, row2_spec),
                out_specs=(P(), P(), P(), P()), check_vma=False))

        multi_v = phases.program("fused_valid", fuse_key, build_multi_valid)
        phases.enter("gbdt_fit_dispatch")
        buf_dev, mbuf_dev, n_done_dev, best_it_dev = multi_v(
            Xbt_d, y_d, w_d, vmask_d, scores_d, Xvb_d, yv_d, wv_d,
            vscores_d)
        phases.enter("gbdt_fit_wait")
        jax.block_until_ready((buf_dev, mbuf_dev, n_done_dev, best_it_dev))
        phases.enter("gbdt_fit_download")
        n_done = int(n_done_dev)
        best_iter = int(best_it_dev)
        # slice on device before downloading: when early stopping fires well
        # before num_iterations, the static buffer's unused zero rows must
        # not cross the host link
        mbuf = np.asarray(mbuf_dev[:n_done])
        history[metric_name].extend(float(x) for x in mbuf)
        _note_valid_evals(metric_name, "device", n_done, nv)
        rows = np.asarray(buf_dev[:n_done])
        for it in range(n_done):
            # each buffer row is one iteration's pack of K stacked trees —
            # the same layout the host loop downloads per iteration
            trees_host, tally = _unpack_fit(rows[it], (K,), cfg)
            tallies.append(tally)
            for k in range(K):
                all_trees.append(jax.tree_util.tree_map(
                    lambda a: a[k], trees_host))
        # falls through to the shared finalize/truncate/rf-scale epilogue

    base_key = jax.random.PRNGKey(seed)
    # watchdog: one beat + one duration report per boosting round — a host
    # loop wedged on a stuck dispatch stops beating and gets stack-dumped;
    # a round suddenly 5x slower than its window trips the throughput
    # sentinel (fused paths have no rounds; scan_eval_history covers them)
    hb = _watchdog.register("gbdt_round_loop", stall_seconds=120.0) \
        if not fuse_es else _watchdog.NOOP_HEARTBEAT
    # last-good-checkpoint dump on watchdog events (opt-in via
    # MMLSPARK_TPU_CHECKPOINT_ON_UNHEALTHY=1): a NaN/divergence sentinel
    # or a stall episode during a checkpointed fit writes the newest
    # HEALTHY state immediately — for sentinels the flagged round's trees
    # are dropped (they embody the bad update), for stalls every complete
    # round is good. The dump rides the normal checkpoint format, so the
    # standard auto-resume picks it up after the operator kills the job.
    unregister_dump = None
    if ckpt_mgr is not None and dump_on_unhealthy:
        dump_once = threading.Event()

        def _last_good_dump(category, name, fields):
            # sentinel events name the model stream ("gbdt"); stall
            # episodes name the heartbeat site
            if name not in ("gbdt", "gbdt_round_loop") or dump_once.is_set():
                return
            trees_snap = list(all_trees)    # append-only: snapshot is safe
            complete = (len(trees_snap) // K) * K
            if category in ("nan_loss", "loss_divergence") and complete >= K:
                complete -= K
            if complete <= 0:
                # nothing healthy to dump yet — stay ARMED: a round-0
                # event must not burn the one-shot latch and silence a
                # real mid-fit dump later
                return
            step = iterations_done + complete // K - 1
            try:
                ckpt_mgr.save(step, {
                    "model": _finalize(trees_snap[:complete]).model_string(),
                    "iteration": step,
                    "fingerprint": ckpt_fingerprint,
                    "prior_iterations": 0 if user_init_booster is None
                    else user_init_booster.num_iterations,
                    "best_metric": best_metric,
                    "best_iter": best_iter,
                    "rounds_no_improve": rounds_no_improve,
                    "history": history,
                    "valid_fingerprint": valid_fp,
                    "emergency": True, "reason": category})
            except Exception:  # noqa: BLE001 — disk full mid-incident:
                return         # stay armed for a later, luckier event
            # latch only AFTER a successful publish — a failed dump must
            # not permanently disable the safety net
            dump_once.set()
            _flight.record("checkpoint_emergency_dump", model="gbdt",
                           reason=category, iteration=step)

        unregister_dump = _watchdog.add_event_callback(_last_good_dump)
    if not fuse_es:
        phases.enter("gbdt_fit_rounds")
    t_round = time.perf_counter()
    try:
        for it in ([] if fuse_es else range(iterations_done, num_iterations)):
            hb.beat()
            # chaos hook: one evaluation per boosting round — `error`
            # kills the fit mid-train (the preemption drill the resume
            # path is tested against), `delay` simulates a slow round
            _failpoint("gbdt.round")
            key, bag_key = _iter_keys(base_key, it)
            scores_d, vscores_d_new, trees_packed, metrics = step(
                Xbt_d, y_d, w_d, vmask_d, scores_d,
                Xvb_d if has_valid else dummy, yv_d if has_valid else dummy,
                wv_d if has_valid else dummy, vscores_d if has_valid else dummy,
                key, bag_key, np.float32(it))
            if has_valid:
                vscores_d = vscores_d_new
            trees_host, tally = _unpack_fit(np.asarray(trees_packed), (K,), cfg)  # graftlint: disable=hot-path-host-sync (deliberate: one tree download per round grows the host forest)
            tallies.append(tally)
            for k in range(K):
                all_trees.append(jax.tree_util.tree_map(lambda a: a[k], trees_host))

            if provide_training_metric and (it % metric_eval_period == 0
                                            or it == num_iterations - 1):
                history.setdefault(f"training_{metric_name}", []).append(
                    float(metrics["train"]))  # graftlint: disable=hot-path-host-sync (deliberate per-eval-period metric download)

            if has_valid and (it % metric_eval_period == 0 or it == num_iterations - 1):
                m = float(metrics["valid"])  # graftlint: disable=hot-path-host-sync (deliberate per-eval-period metric download)
                _note_valid_evals(metric_name, "host", 1, nv)
                history[metric_name].append(m)
                _watchdog.report_training_metric("gbdt", it, loss=m,
                                                 metric_name=metric_name)
                improved = (m > best_metric + es_tol if higher_is_better
                            else m < best_metric - es_tol)
                if improved:
                    best_metric, best_iter, rounds_no_improve = m, it, 0
                else:
                    rounds_no_improve += 1
                if iteration_callback is not None:
                    iteration_callback(it, {metric_name: m})
                if early_stopping_rounds > 0 and rounds_no_improve >= early_stopping_rounds:
                    break
            elif iteration_callback is not None:
                iteration_callback(it, {})
            now_round = time.perf_counter()
            _watchdog.report_training_metric("gbdt", it,
                                             seconds=now_round - t_round)
            t_round = now_round

            if (ckpt_mgr is not None and checkpoint_period > 0
                    and (it + 1) % checkpoint_period == 0
                    and it + 1 < num_iterations):
                # the accumulated score matrices ride in the payload so a
                # resume restarts from the EXACT optimizer state — see the
                # resume_scores comment above (bit-identical trees). One
                # d2h per checkpoint period; best-effort on exotic
                # placements (a non-addressable mesh falls back to the
                # predict_raw reconstruction on resume).
                try:
                    scores_host = np.asarray(scores_d)[:n]  # graftlint: disable=hot-path-host-sync (deliberate: one d2h per checkpoint period, exact-state resume needs the host copy)
                    vscores_host = (np.asarray(vscores_d)[:nv]  # graftlint: disable=hot-path-host-sync (same deliberate checkpoint d2h as scores_host)
                                    if has_valid else None)
                except Exception:  # noqa: BLE001
                    scores_host = vscores_host = None
                ckpt_mgr.save(it, {"model": _finalize(all_trees).model_string(),
                                   "iteration": it,
                                   "fingerprint": ckpt_fingerprint,
                                   "prior_iterations":
                                       0 if user_init_booster is None
                                       else user_init_booster.num_iterations,
                                   "best_metric": best_metric,
                                   "best_iter": best_iter,
                                   "rounds_no_improve": rounds_no_improve,
                                   "history": history,
                                   "scores": scores_host,
                                   "vscores": vscores_host,
                                   "valid_fingerprint": valid_fp})

    finally:
        hb.close()
        if unregister_dump is not None:
            unregister_dump()
    phases.enter("gbdt_fit_finalize")
    _tell_run_tally(fit, cfg, nshards, K, tallies)
    booster = _finalize(all_trees)
    # early-stop truncation applies to fresh runs and checkpoint resumes
    # alike (the checkpoint's trees carry global iteration indices); only a
    # user-supplied warm-start booster suppresses it, as before.
    if (early_stopping_rounds > 0 and best_iter >= 0
            and user_init_booster is None):
        booster = _truncate_booster(booster, best_iter + 1)
    if is_rf:
        # forest prediction = base + average of (unshrunk) trees
        booster = _scale_booster_values(
            booster, np.full(booster.num_trees, 1.0 / booster.num_iterations))
    return booster


def _scale_booster_values(b: Booster, per_tree_scale: np.ndarray) -> Booster:
    """Scale each tree's output values (rf averaging / dart normalization)."""
    s = np.asarray(per_tree_scale, np.float32)[:, None]
    trees = b.trees._replace(
        leaf_value=np.asarray(b.trees.leaf_value) * s,
        node_value=np.asarray(b.trees.node_value) * s)
    return Booster(trees, b.thr_raw, b.num_class, b.base_score, b.objective,
                   b.depth_cap, b.binner_state, b.best_iteration,
                   b.eval_history, b.objective_kwargs)


def _train_dart(*, mesh, cfg, K, obj, objective, objective_kwargs,
                Xbt_d, y_d, w_d, vmask_d, base, has_valid, Xvb_d, yv_d, wv_d,
                depth_cap, metric_name, num_iterations, seed,
                feature_fraction, use_bagging, bagging_fraction, bagging_freq,
                early_stopping_rounds, iteration_callback, metric_eval_period,
                early_stopping_tolerance=0.0,
                drop_rate, max_drop, skip_drop, drop_seed,
                binner, max_bin, is_cat_j=None, phases) -> Booster:
    """DART boosting: Dropouts meet Multiple Additive Regression Trees.

    Parity target: LightGBM's ``boosting=dart`` (reference exposes it via
    TrainParams.scala:9-10). Per iteration, each existing tree is dropped
    with probability ``drop_rate`` (skipped entirely with probability
    ``skip_drop``, capped at ``max_drop``); the new tree fits gradients at
    the ensemble *without* the dropped trees; then the new tree is scaled by
    1/(k+1) and the dropped trees by k/(k+1) (DART-paper normalization, the
    LightGBM default mode).

    TPU design: per-tree training-row contributions are kept as one sharded
    [T, n, K] device array so "the ensemble minus dropped trees" is a single
    weighted reduction with a host-supplied per-tree scale vector — no
    re-walking historical trees. Early stopping records best_iteration but
    does not truncate (dropping later trees would denormalize earlier ones).
    """
    F, npad = Xbt_d.shape
    T_max = num_iterations
    shards = meshlib.num_shards(mesh)
    grow = (grow_tree_depthwise if cfg.growth_policy == "depthwise"
            else grow_tree)
    grow_axis = _grow_axis_for(mesh, cfg)
    base_j = jnp.asarray(base)

    def dart_step_local(binned_t, yl, wl, vmask, contribs, eff_scales,
                        vbinned, vcontribs, key, bag_key, it_i):
        scores = base_j[None, :] + jnp.einsum("t,tnk->nk", eff_scales,
                                              contribs)
        if K > 1:
            grad, hess = obj.grad_hess(scores, yl, wl)
        else:
            grad, hess = obj.grad_hess(scores[:, 0], yl, wl)
            grad, hess = grad[:, None], hess[:, None]
        if use_bagging:
            k2 = jax.random.fold_in(bag_key, jax.lax.axis_index("data"))
            bag = jax.random.uniform(k2, vmask.shape) < bagging_fraction
            row_mask = vmask * bag.astype(jnp.float32)
        else:
            row_mask = vmask
        fmask = jnp.ones(F, dtype=bool)
        if feature_fraction < 1.0:
            fkey = jax.random.fold_in(key, 7)
            u = jax.random.uniform(fkey, (F,))
            fmask = (u < feature_fraction).at[jnp.argmin(u)].set(True)
        trees_out, new_contrib, tallies = [], [], []
        for k in range(K):
            tree, row_node, tally = _grow_with_warmup(
                grow, it_i, cfg, jax.random.fold_in(key, 13 + k),
                binned_t, grad[:, k], hess[:, k], row_mask, fmask,
                axis_name=grow_axis, is_cat=is_cat_j)
            new_contrib.append(tree.leaf_value[row_node])
            trees_out.append(tree)
            tallies.append(tally)
        nc = jnp.stack(new_contrib, axis=1)                # [n_local, K]
        contribs = lax.dynamic_update_slice(contribs, nc[None], (it_i, 0, 0))
        trees_stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *trees_out)
        if has_valid:
            vc = jnp.stack(
                [predict_tree_binned(
                    jax.tree_util.tree_map(lambda a: a[k], trees_stacked),
                    vbinned, is_cat=is_cat_j)
                 for k in range(K)], axis=1)
            vcontribs = lax.dynamic_update_slice(
                vcontribs, vc[None], (it_i, 0, 0))
        # one flat download buffer instead of 13 per-field transfers
        return contribs, vcontribs, pack_trees(trees_stacked,
                                               jnp.stack(tallies))

    def dart_eval_local(vcontribs, scales, vy, vw):
        sc2 = base_j[None, :] + jnp.einsum("t,tnk->nk", scales, vcontribs)
        sc = sc2 if K > 1 else sc2[:, 0]
        _, num = eval_metric(obj, sc, vy, vw, **objective_kwargs)
        return _combine_metric(num, jnp.sum(vw), metric_name)

    row_spec, row2_spec = P("data"), P("data", None)
    col_spec = P(None, "data")
    c_spec = P(None, "data", None)
    # compiled-step cache, same rationale as the gbdt path: the closures are
    # fresh per fit() call, so jit's identity-keyed cache would recompile on
    # every trial of a sweep (the resolved histogram engine keys it for the
    # same reason as the gbdt step cache)
    from ...ops.histogram import resolve_engine as _resolve_hist_engine
    cache_key = ("dart", _resolve_hist_engine(), cfg, K, objective,
                 tuple(sorted(objective_kwargs.items())),
                 None if is_cat_j is None
                 else tuple(np.flatnonzero(np.asarray(is_cat_j)).tolist()),
                 Xbt_d.shape,
                 None if not has_valid else Xvb_d.shape, T_max,
                 use_bagging, bagging_fraction, bagging_freq,
                 feature_fraction, depth_cap, metric_name,
                 tuple(np.asarray(base).tolist()), mesh)
    def build_dart():
        dstep = jax.jit(shard_map(
            dart_step_local, mesh=mesh,
            in_specs=(col_spec, row_spec, row_spec, row_spec, c_spec, P(),
                      col_spec if has_valid else P(),
                      c_spec if has_valid else P(), P(), P(), P()),
            out_specs=(c_spec, c_spec if has_valid else P(), P()),
            check_vma=False))
        deval = (jax.jit(shard_map(
            dart_eval_local, mesh=mesh,
            in_specs=(c_spec, P(), row_spec, row_spec), out_specs=P(),
            check_vma=False)) if has_valid else None)
        return dstep, deval

    phases.parent.set(path="dart", program=_hit_or_built(cache_key))
    dstep, deval = _cached_program(cache_key, build_dart)

    sh = lambda spec: placement.sharding(spec, mesh)
    contribs_d = placement.device_put(
        np.zeros((T_max, npad, K), np.float32), sh(c_spec))
    vcontribs_d = (placement.device_put(
        np.zeros((T_max, Xvb_d.shape[1], K), np.float32), sh(c_spec))
        if has_valid else np.zeros((), np.float32))
    dummy = np.zeros((), np.float32)

    scales = np.zeros(T_max, np.float32)
    rng_drop = np.random.default_rng(drop_seed)
    all_trees: List[Tree] = []
    tallies: List[np.ndarray] = []    # the run tally of every download
    history: Dict[str, List[float]] = {metric_name: []}
    higher_is_better = metric_name in HIGHER_IS_BETTER
    es_tol = float(early_stopping_tolerance)
    best_metric = -np.inf if higher_is_better else np.inf
    best_iter, rounds_no_improve = -1, 0
    base_key = jax.random.PRNGKey(seed)

    # The drop sets depend only on the numpy RNG stream, never on data, so
    # the whole schedule + scale evolution precomputes up front; BOTH the
    # fused dispatch and the host loop consume these rows, so there is one
    # copy of the drop/scale logic (eff_rows[it] = scales entering
    # iteration it with its drop set zeroed; post_rows[it] = scales after
    # the iteration's DART renormalization).
    eff_rows = np.zeros((T_max, T_max), np.float32)
    post_rows = np.zeros((T_max, T_max), np.float32)
    for it in range(T_max):
        if it == 0 or rng_drop.uniform() < skip_drop:
            dropped = np.empty(0, np.int64)
        else:
            dropped = np.nonzero(rng_drop.uniform(size=it) < drop_rate)[0]
            if max_drop > 0 and len(dropped) > max_drop:
                dropped = rng_drop.choice(dropped, size=max_drop,
                                          replace=False)
        eff_rows[it] = scales
        eff_rows[it, dropped] = 0.0
        kdrop = len(dropped)
        scales[dropped] *= kdrop / (kdrop + 1.0)
        scales[it] = 1.0 / (kdrop + 1.0)
        post_rows[it] = scales

    # --- fused dart: the entire run in ONE device dispatch — a scan
    # without validation, the shared _fused_es_scan while_loop with
    # on-device early stopping with it (the host loop pays a device
    # round-trip per iteration).
    fuse_dart = (iteration_callback is None
                 and (not has_valid or metric_eval_period == 1)
                 and not os.environ.get("MMLSPARK_TPU_DISABLE_FUSED_DART"))  # graftlint: disable=resolve-before-cache-key (gates the fused path off entirely; never feeds a key)
    if fuse_dart:
        fuse_key = (cache_key, num_iterations, seed, early_stopping_rounds,
                    es_tol, "dart_fused")

        def build_dart_fused():
            def multi_local(binned_l, yl, wl, vmask_l, contribs_l,
                            vbinned_l, vcontribs_l, eff_mat, post_mat,
                            vy_l, vw_l):
                def one_iter(it, state):
                    contribs_c, vcontribs_c = state
                    key = jax.random.fold_in(base_key, it)
                    bag_step = (it // max(bagging_freq, 1)
                                if use_bagging else 0)
                    bag_key = jax.random.fold_in(base_key,
                                                 1_000_003 + bag_step)
                    contribs_c, vcontribs_c, packed = dart_step_local(
                        binned_l, yl, wl, vmask_l, contribs_c, eff_mat[it],
                        vbinned_l, vcontribs_c, key, bag_key, it)
                    if has_valid:
                        m = dart_eval_local(vcontribs_c, post_mat[it],
                                            vy_l, vw_l).astype(jnp.float32)
                    else:
                        m = jnp.float32(jnp.nan)
                    return (contribs_c, vcontribs_c), packed, m

                return _fused_es_scan(one_iter, (contribs_l, vcontribs_l),
                                      num_iterations, early_stopping_rounds,
                                      higher_is_better,
                                      track_metric=has_valid, tol=es_tol)

            return jax.jit(shard_map(
                multi_local, mesh=mesh,
                in_specs=(col_spec, row_spec, row_spec, row_spec, c_spec,
                          col_spec if has_valid else P(),
                          c_spec if has_valid else P(), P(), P(),
                          row_spec if has_valid else P(),
                          row_spec if has_valid else P()),
                out_specs=(P(), P(), P(), P()), check_vma=False))

        multi_d = phases.program("dart", fuse_key, build_dart_fused)
        phases.enter("gbdt_fit_dispatch")
        buf_dev, mbuf_dev, n_done_dev, best_it_dev = multi_d(
            Xbt_d, y_d, w_d, vmask_d, contribs_d,
            Xvb_d if has_valid else dummy,
            vcontribs_d if has_valid else dummy,
            jnp.asarray(eff_rows), jnp.asarray(post_rows),
            yv_d if has_valid else dummy,
            wv_d if has_valid else dummy)
        phases.enter("gbdt_fit_wait")
        jax.block_until_ready((buf_dev, mbuf_dev, n_done_dev, best_it_dev))
        phases.enter("gbdt_fit_download")
        n_done = int(n_done_dev)
        best_iter = int(best_it_dev)
        if has_valid:
            # device-side slice: don't download unexecuted zero rows
            history[metric_name].extend(
                float(x) for x in np.asarray(mbuf_dev[:n_done]))
        rows = np.asarray(buf_dev[:n_done])
        for it in range(n_done):
            trees_host, tally = _unpack_fit(rows[it], (K,), cfg)
            tallies.append(tally)
            for k in range(K):
                all_trees.append(jax.tree_util.tree_map(
                    lambda a: a[k], trees_host))
        # the per-tree scale vector is the post-step scales of the last
        # executed iteration — identical to the host loop's final `scales`
        scales = post_rows[n_done - 1].copy()
        phases.enter("gbdt_fit_finalize")
        _tell_run_tally(phases.parent, cfg, shards, K, tallies)
        booster = _finalize_trees(all_trees, binner, max_bin, K, base,
                                  objective, depth_cap, objective_kwargs,
                                  best_iter, history, None)
        return _scale_booster_values(booster,
                                     np.repeat(scales[:n_done], K))

    hb = _watchdog.register("gbdt_dart_round_loop", stall_seconds=120.0)
    phases.enter("gbdt_fit_rounds")
    t_round = time.perf_counter()
    try:
        for it in range(num_iterations):
            hb.beat()
            key = jax.random.fold_in(base_key, it)
            bag_step = it // max(bagging_freq, 1) if use_bagging else 0
            bag_key = jax.random.fold_in(base_key, 1_000_003 + bag_step)
            contribs_d, vcontribs_new, trees_packed = dstep(
                Xbt_d, y_d, w_d, vmask_d, contribs_d,
                jnp.asarray(eff_rows[it]),
                Xvb_d if has_valid else dummy,
                vcontribs_d if has_valid else dummy,
                key, bag_key, np.int32(it))
            if has_valid:
                vcontribs_d = vcontribs_new
            trees_host, tally = _unpack_fit(np.asarray(trees_packed), (K,), cfg)  # graftlint: disable=hot-path-host-sync (deliberate: one tree download per round grows the host forest)
            tallies.append(tally)
            for k in range(K):
                all_trees.append(jax.tree_util.tree_map(lambda a: a[k],
                                                        trees_host))
            scales = post_rows[it].copy()

            if has_valid and (it % metric_eval_period == 0
                              or it == num_iterations - 1):
                m = float(deval(vcontribs_d, jnp.asarray(scales), yv_d, wv_d))  # graftlint: disable=hot-path-host-sync (deliberate per-eval-period metric download)
                history[metric_name].append(m)
                _watchdog.report_training_metric("gbdt", it, loss=m,
                                                 metric_name=metric_name)
                improved = (m > best_metric + es_tol if higher_is_better
                            else m < best_metric - es_tol)
                if improved:
                    best_metric, best_iter, rounds_no_improve = m, it, 0
                else:
                    rounds_no_improve += 1
                if iteration_callback is not None:
                    iteration_callback(it, {metric_name: m})
                if (early_stopping_rounds > 0
                        and rounds_no_improve >= early_stopping_rounds):
                    break
            elif iteration_callback is not None:
                iteration_callback(it, {})
            now_round = time.perf_counter()
            _watchdog.report_training_metric("gbdt", it,
                                             seconds=now_round - t_round)
            t_round = now_round

    finally:
        hb.close()
    phases.enter("gbdt_fit_finalize")
    _tell_run_tally(phases.parent, cfg, shards, K, tallies)
    booster = _finalize_trees(all_trees, binner, max_bin, K, base, objective,
                              depth_cap, objective_kwargs, best_iter, history,
                              None)
    n_done = len(all_trees) // K
    per_tree = np.repeat(scales[:n_done], K)
    return _scale_booster_values(booster, per_tree)


def _finalize_trees(trees_list: List[Tree], binner, max_bin: int, K: int,
                    base, objective: str, depth_cap: int,
                    objective_kwargs: Optional[dict], best_iter: int,
                    history: Dict[str, List[float]],
                    init_booster: Optional[Booster]) -> Booster:
    """Stack grown trees into a Booster (raw thresholds from bin bounds)."""
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *trees_list)
    upper = binner.bin_upper_raw()  # [F, B]
    thr_raw = upper[stacked.feat, np.minimum(stacked.thr_bin, max_bin - 1)]
    thr_raw = np.where(stacked.is_leaf, np.float32(np.inf), thr_raw)
    b = Booster(stacked, thr_raw.astype(np.float32), K, base,
                objective, depth_cap, binner.state(),
                best_iteration=best_iter, eval_history=history,
                objective_kwargs=objective_kwargs)
    if init_booster is not None:
        b = _merge_boosters(init_booster, b)
    return b


def _truncate_booster(b: Booster, num_iterations: int) -> Booster:
    t_end = num_iterations * b.num_class
    trees = jax.tree_util.tree_map(lambda a: a[:t_end], b.trees)
    out = Booster(trees, b.thr_raw[:t_end], b.num_class, b.base_score,
                  b.objective, b.depth_cap, b.binner_state, b.best_iteration,
                  b.eval_history, b.objective_kwargs)
    if b.missing_dec is not None:
        out.missing_dec = b.missing_dec[:t_end]
    return out


def _pad_tree_slots(trees: Tree, thr: np.ndarray, M: int):
    """Widen fixed-shape tree arrays to M node slots (inert leaf padding)."""
    cur = trees.feat.shape[1]
    if cur == M:
        return trees, thr
    pad = M - cur

    def pad_field(name, a):
        a = np.asarray(a)
        if a.ndim == 1:          # per-tree scalars (node_count)
            return a
        fill = {"is_leaf": True}.get(name, 0)
        width = [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
        return np.pad(a, width, constant_values=fill)

    trees = Tree(**{k: pad_field(k, v)
                    for k, v in trees._asdict().items()})
    thr = np.pad(thr, ((0, 0), (0, pad)), constant_values=np.float32(np.inf))
    return trees, thr


def _merge_boosters(first: Booster, second: Booster) -> Booster:
    """Concatenate tree sequences (BoosterMerge parity,
    reference: TrainUtils.scala:165-168 warm-start via LGBM_BoosterMerge).

    Slot widths may differ (e.g. a warm start loaded from a LightGBM text
    model vs freshly grown trees): both sides are padded to the wider M."""
    assert first.num_class == second.num_class
    M = max(first.trees.feat.shape[1], second.trees.feat.shape[1])
    # bitset word widths may also differ (e.g. max_bin 63 vs 255 models)
    BW = max(first.trees.cat_bitset.shape[-1],
             second.trees.cat_bitset.shape[-1])

    def widen_bits(t: Tree) -> Tree:
        cur = t.cat_bitset.shape[-1]
        if cur == BW:
            return t
        return t._replace(cat_bitset=np.pad(
            np.asarray(t.cat_bitset), ((0, 0), (0, 0), (0, BW - cur))))

    t1, thr1 = _pad_tree_slots(widen_bits(first.trees), first.thr_raw, M)
    t2, thr2 = _pad_tree_slots(widen_bits(second.trees), second.thr_raw, M)
    trees = jax.tree_util.tree_map(
        lambda a, c: np.concatenate([np.asarray(a), np.asarray(c)], axis=0),
        t1, t2)
    thr = np.concatenate([thr1, thr2], axis=0)
    out = Booster(trees, thr, first.num_class, first.base_score, second.objective,
                  max(first.depth_cap, second.depth_cap), second.binner_state,
                  second.best_iteration, second.eval_history, second.objective_kwargs)
    if first.missing_dec is not None or second.missing_dec is not None:
        # absent side = the framework's own semantics (decision_type 10)
        def _md(b, t):
            if b.missing_dec is not None:
                md = b.missing_dec
                return np.pad(md, ((0, 0), (0, M - md.shape[1])),
                              constant_values=10)
            return np.full((t.feat.shape[0], M), 10, np.uint8)
        out.missing_dec = np.concatenate([_md(first, t1), _md(second, t2)],
                                         axis=0)
    return out
