"""Persistent XLA compilation cache — one init funnel for the framework.

Training a booster compiles multi-second XLA programs (the fused
multi-iteration scan, the per-round step, the device predictor). Within a
process those are amortized by the in-memory program caches
(``_STEP_CACHE`` / ``_PREDICT_CACHE``), but every NEW process — a serving
worker fleet, repeat CLI fits, a chip run — pays the cold compile again.
jax's persistent compilation cache keys compiled executables on (HLO,
compile options, backend version) and stores them on disk, so identical
programs skip XLA entirely across processes.

One rule, in :func:`ensure` (booster fit/predict paths, bundles, ``bench.py``
and ``chip_smoke.py`` all call it): where ``JAX_COMPILATION_CACHE_DIR`` is
set, jax already uses that directory and nothing here sets another; where
it is not, a checkout keeps its cache at the fixed ``<checkout>/.jax_cache``
— never a temp name, pid or timestamp, because the path is part of what
makes two processes find each other's entries. An installed package
(nothing of the checkout beside it — typically a read-only
``site-packages``) gets no directory from here: place the cache with the
variable. Cache *hits* and *misses* are surfaced as the
``persistent_compile_cache_hits_total`` / ``..._misses_total`` counters (fed
by jax's own monitoring events), and every compile/program_build flight
event records the active ``persistent_cache`` dir — that is what the
warm-start test asserts on.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

_ENV = "JAX_COMPILATION_CACHE_DIR"
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# <checkout>/.jax_cache (git-ignored), derived from the package location;
# None for an installed package, whose parent directory is not a checkout
DEFAULT_DIR: Optional[str] = (
    os.path.join(_ROOT, ".jax_cache")
    if os.path.isfile(os.path.join(_ROOT, "pyproject.toml")) else None)

_LOCK = threading.Lock()
_INITIALIZED = False
_DIR: Optional[str] = None
_SOURCE: Optional[str] = None


def cache_dir() -> Optional[str]:
    """The active persistent-cache directory (None before :func:`ensure`,
    and after it when no directory applies)."""
    return _DIR


def cache_source() -> Optional[str]:
    """Where the active directory came from: ``env:JAX_COMPILATION_CACHE_DIR``,
    ``default`` (the in-checkout path), ``fallback`` (a caller-supplied
    directory, e.g. a serving bundle's ``xla_cache/``) or ``unset`` (an
    installed package without the variable: no persistent cache)."""
    return _SOURCE


def ensure(fallback_dir: Optional[str] = None) -> Optional[str]:
    """Idempotently wire jax's persistent compilation cache; returns the
    active directory. First call wins — jax reads the flag at compile time,
    so flipping it mid-process would split programs across caches.

    ``fallback_dir`` replaces the in-checkout default when (and only when)
    ``JAX_COMPILATION_CACHE_DIR`` is unset: the serving-bundle paths
    (``mmlspark_tpu/bundles``) pass the bundle's own ``xla_cache/`` so
    bundle build populates it and bundle prewarm reads it, without
    overriding a cache placed from outside.
    """
    global _INITIALIZED, _DIR, _SOURCE
    with _LOCK:
        if _INITIALIZED:
            return _DIR
        # jax stays a lazy import: `bundles inspect` reads manifests on
        # boxes without an accelerator runtime
        import jax
        from jax import monitoring
        from jax._src import compilation_cache as _jcc
        _INITIALIZED = True
        if (os.environ.get(_ENV) or "").strip():
            # jax read the variable into its own config at import; report
            # what jax will actually use and set no directory in code
            _DIR = jax.config.jax_compilation_cache_dir
            _SOURCE = f"env:{_ENV}"
        elif (fallback_dir or "").strip():
            _DIR, _SOURCE = fallback_dir.strip(), "fallback"
        else:
            _DIR, _SOURCE = DEFAULT_DIR, "default" if DEFAULT_DIR else "unset"
        if _SOURCE in ("fallback", "default"):
            jax.config.update("jax_compilation_cache_dir", _DIR)
        # cache every program: the default 1 s floor would skip most of
        # the small per-shape programs that dominate cold-start count
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax memoizes "is the cache used?" at the FIRST compile of the
        # process (compilation_cache._cache_checked); anything that
        # compiled before this funnel ran — framework import side effects,
        # a warmup op — would have frozen the answer at False and every
        # later compile would silently skip the dir. Reset the memo so the
        # cache engages from here on.
        _jcc.reset_cache()
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        return _DIR


_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "persistent_compile_cache_hits_total",
    "/jax/compilation_cache/cache_misses":
        "persistent_compile_cache_misses_total",
}


def _on_event(event: str, **kwargs) -> None:
    """Feed jax's cache hit/miss monitoring events into the metrics
    registry: ``persistent_compile_cache_hits_total`` is the deterministic
    signal that a warm cache dir actually skipped recompilation (wall-time
    comparisons are flaky on loaded boxes), ``..._misses_total`` the count
    of programs this process had to compile cold."""
    counter = _EVENT_COUNTERS.get(event)
    if counter:
        from ..observability import metrics as _metrics
        _metrics.safe_counter(counter).inc()


_STAGE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "gbdt_jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "gbdt_jax_lower",
    # compile_or_get_cached as a whole: a persistent-cache read is inside it
    "/jax/core/compile/backend_compile_duration": "gbdt_xla_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "gbdt_cache_load",
}


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    """jax's compile stages as children of the span they ran under (a first
    ``gbdt_fit_dispatch`` holds the fit program's trace, lowering and
    compile or cache load). jax reports each stage when it ends, so the
    span is recorded finished; outside any span nothing is recorded. A jit
    traced inside another reports its own trace stage nested in the outer
    one's — one fit program holds some 350 such traces of ``jnp`` helpers,
    microseconds each — so stages under a millisecond are left out."""
    name = _STAGE_SPANS.get(event)
    if name and duration_secs >= 1e-3:
        from ..observability import spans as _spans
        _spans.record_finished(name, duration_secs, **kwargs)
