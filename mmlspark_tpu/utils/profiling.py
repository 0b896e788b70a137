"""Device-level tracing hooks around the XLA profiler.

The reference's tracing story is host-side wall-clock scopes (StopWatch
feeding VW's TrainingStats — core/utils/StopWatch.scala,
vw/VowpalWabbitBase.scala:27-46 — and the Timer stage,
stages/Timer.scala:57-92). On TPU the interesting time is *inside* the
device program, which host timers cannot see — SURVEY §5's mapping for this
subsystem is "replace with jax profiler hooks + per-stage timing stats
surfaced the same way". This module is that replacement:

- :func:`trace` wraps ``jax.profiler.trace``: captures an XLA device trace
  (MXU occupancy, HBM traffic, fusion boundaries) viewable in
  TensorBoard/Perfetto. Works on CPU too, so tests cover it without
  hardware.
- :func:`annotate` / :func:`annotate_fn` name host-side regions so device
  ops launched inside them carry the label in the trace — the analog of the
  reference's per-scope StopWatch names.
- :func:`device_memory_stats` surfaces live per-device HBM usage — the
  operational complement to the binned-dataset cache's documented HBM
  retention (models/gbdt/api.py).

Where a backend lacks the profiler's device hooks, every entry point
degrades to a no-op (with the reason recorded) rather than failing the
pipeline it instruments.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator, Optional

from ..observability.logging import get_logger

logger = get_logger(__name__)

__all__ = ["trace", "annotate", "annotate_fn", "device_memory_stats"]


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture an XLA profiler trace of everything dispatched inside the
    ``with`` block into ``log_dir`` (TensorBoard ``profile`` plugin /
    Perfetto format). No-op (but still a valid context) if the profiler
    cannot start — e.g. a second concurrent trace, or a backend without
    profiler support."""
    import jax

    try:
        jax.profiler.start_trace(log_dir,
                                 create_perfetto_link=create_perfetto_link)
        started = True
    except Exception as e:  # noqa: BLE001 — degrade to no-op, never break
        logger.warning("profiler trace unavailable (%r); continuing "
                       "untraced", e)
        started = False
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                logger.warning("profiler stop_trace failed: %r", e)


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Label device work launched in this region: ops dispatched inside show
    up under ``name`` in profiler traces (jax.profiler.TraceAnnotation)."""
    import jax

    entered = False
    try:
        ctx = jax.profiler.TraceAnnotation(name)
        ctx.__enter__()
        entered = True
    except Exception as e:  # noqa: BLE001 — never break the annotated job
        logger.warning("profiler annotation %r unavailable: %r", name, e)
    try:
        yield
    finally:
        if entered:
            try:
                ctx.__exit__(None, None, None)
            except Exception as e:  # noqa: BLE001
                logger.warning("profiler annotation %r exit failed: %r",
                               name, e)


def annotate_fn(name: str):
    """Decorator form of :func:`annotate`."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with annotate(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def device_memory_stats() -> Dict[str, Optional[Dict[str, Any]]]:
    """Live per-device memory stats keyed by device string (``bytes_in_use``,
    ``peak_bytes_in_use``, … as reported by PJRT). Devices whose runtime
    does not expose stats (the CPU backend) map to ``None``."""
    import jax

    out: Dict[str, Optional[Dict[str, Any]]] = {}
    for dev in jax.devices():
        try:
            ms = dev.memory_stats()
            out[str(dev)] = dict(ms) if ms is not None else None
        except Exception as e:  # noqa: BLE001
            logger.warning("memory_stats unavailable on %s: %r", dev, e)
            out[str(dev)] = None
    return out
