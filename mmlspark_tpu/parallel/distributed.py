"""Multi-host initialization — replaces the driver-socket rendezvous protocol.

The reference bootstraps distributed training with a driver ServerSocket that
collects each task's host:port and broadcasts ring membership
(reference: lightgbm/LightGBMUtils.scala:116-185, LightGBMConstants.scala:34-40),
then hands off to per-learner TCP collectives. On TPU the runtime already has a
gang-scheduled SPMD world: ``jax.distributed.initialize`` plus a Mesh spanning
all hosts' devices gives membership, barriers, and collectives over ICI/DCN.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax._src import distributed as _jdist


def _coordination_client():
    """JAX's distributed coordination client, or None before
    ``jax.distributed.initialize``. Read from private state
    (``jax._src.distributed.global_state``, where the installed jax keeps
    it) because the public alternatives (``jax.process_count()``)
    initialize the XLA backend, and ``jax.distributed.initialize`` must run
    before any backend touch — see the ordering notes at the call sites."""
    return _jdist.global_state.client


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Initialize the multi-host JAX runtime (no-op on a single process).

    On Cloud TPU all three arguments are auto-detected from the metadata server;
    elsewhere they mirror the reference's (driverHost, numTasks, partitionId)
    triple (LightGBMUtils.scala:116-185) but with exactly-once semantics and no
    bespoke socket protocol.
    """
    # Guard against double-init WITHOUT touching the XLA backend:
    # jax.process_count() would initialize it, and jax.distributed must run
    # first (this exact ordering bug is why the guard reads internal state).
    if _coordination_client() is not None:
        # already initialized (possibly directly or by another
        # framework): still stamp the process index, or multi-host
        # trace events fall back to os.getpid(), which can collide
        # across hosts and interleave merged dumps into one pid track
        _tag_spans_with_process_index()
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except Exception:
        if coordinator_address is not None or num_processes is not None or \
                "JAX_COORDINATOR_ADDRESS" in os.environ:
            raise  # explicit multi-host request must not be swallowed
        # auto-detection unavailable (single host, no metadata server): fine
        return
    _tag_spans_with_process_index()


def _tag_spans_with_process_index() -> None:
    """Stamp this host's process index onto every subsequent telemetry
    event (observability.spans uses it as the Chrome-trace pid), so merged
    multi-host trace dumps separate by process. Backend is safe to touch
    here: jax.distributed.initialize has already run."""
    try:
        from ..observability import flight as _flight
        from ..observability import logging as _logging
        from ..observability import metrics as _metrics
        from ..observability import spans as _spans
        if not _metrics.enabled():
            # jax.process_index() creates the XLA backend as a side
            # effect — don't pay (or force) backend startup to stamp an
            # attribute the disabled telemetry layer will never record
            return
        idx = jax.process_index()
        _spans.set_default_attrs(process_index=idx)
        # same stamp on flight events AND log records, so merged
        # post-mortem dumps / log streams from several hosts separate by
        # process the way trace dumps do
        _flight.set_default_fields(process_index=idx)
        _logging.set_default_fields(process_index=idx)
        _flight.record("distributed_init", process_index=idx,
                       process_count=jax.process_count())
        _logging.get_logger("mmlspark_tpu.parallel").info(
            "distributed runtime initialized", process_index=idx,
            process_count=jax.process_count())
    except Exception:  # noqa: BLE001 — telemetry must never break init
        pass


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    return jax.process_index() == 0


def barrier(name: str = "barrier") -> None:
    """Global barrier (gang scheduling is inherent on TPU; this is for host code).

    Replaces Spark barrier execution mode (reference: TrainUtils.scala:476-483).
    """
    # Read the coordination client BEFORE any jax.* call that could
    # initialize the XLA backend: a pre-init backend touch here would both
    # no-op the barrier and poison a later initialize() (same ordering
    # hazard as in initialize() above).
    client = _coordination_client()
    if client is None:
        if jax.process_count() == 1:
            return                      # single process: barrier is a no-op
        raise RuntimeError("no distributed client; call initialize() first")
    from ..observability import watchdog as _watchdog
    from ..observability.spans import span as _span
    # watchdog heartbeat across the wait: a peer that never arrives makes
    # this process hang here — the stalled-barrier state the watchdog
    # exists to flag (stuck collectives, not crashes, are how pods fail).
    # 90 s floor: a wait up to the barrier's own 60 s timeout is legal
    # (one host finishing a long compile late); only a wait_at_barrier
    # that overruns its contract — a stuck coordination RPC — flags.
    from ..robustness.failpoints import fault_point as _failpoint
    with _watchdog.register(f"barrier:{name}", stall_seconds=90.0), \
            _span(f"barrier.{name}", metric_label="barrier", barrier=name):
        # chaos hook: a peer stuck (delay) or lost (error) at the barrier
        _failpoint("barrier.wait")
        client.wait_at_barrier(name, timeout_in_ms=60_000)
