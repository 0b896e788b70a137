"""Pre-pinned staging buffers: the zero-copy half of continuous batching.

The threaded engine builds every device batch as list-of-rows ->
``np.asarray`` — one full Python-side copy per batch, on the scoring
thread, while the device waits. Here the copy disappears: rows decode
straight into a pre-allocated ``[slots, width]`` staging array at
ADMISSION time (on the event loop, overlapped with device compute),
and the scoring call receives a pow2-bucket *view* of that array — the
only remaining transfers are the one h2d the fused predictor performs
through ``parallel/placement.py`` and its one d2h.

Two ping-pong buffers make this safe without copies: the loop fills
the FORMING buffer while the scoring thread reads the DISPATCHED one;
:meth:`SlotTable.flip` swaps them at dispatch. One scoring thread owns
the device (the PR 2 executable cache is process-wide but the round
loop is single-owner), so two buffers are exactly enough.

Sizing: ``slots`` is the device-batch slot count — the pow2 bucket cap
the compiled predictor sees. ``MMLSPARK_TPU_ASERVE_SLOTS`` overrides
it fleet-wide (0 keeps the per-query ``max_batch``); the admission
backlog bound stays ``MMLSPARK_TPU_MAX_QUEUE_DEPTH``, shared with the
threaded engine.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ...observability import hbm as _hbm
from ...observability.env_registry import env_int
from ..serving import bucket_size

SLOTS_ENV = "MMLSPARK_TPU_ASERVE_SLOTS"


def _pow2_ceil(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def resolve_slots(max_batch: int, row_bytes: "int | None" = None) -> int:
    """The effective slot count: the env override when set (>0), else
    ``max_batch``; always rounded up to a power of two so the bucket
    ladder is exact.

    ``MMLSPARK_TPU_ASERVE_SLOTS=auto`` asks the auto-tuner (tuning
    site 3) for the measured size — the p99.9 of observed admitted-batch
    rows reconciled against the ``aserve_slots`` HBM claim headroom. A
    first process with no measured decision sizes statically (the
    untuned rule); the raw-string check matters because ``env_int``
    maps any unparseable value to its default, which would silently turn
    ``auto`` into the static path with no tuner consult."""
    import os

    raw = (os.environ.get(SLOTS_ENV) or "").strip().lower()
    if raw == "auto":
        from ... import tuning as _tuning
        tuned = _tuning.resolve_slots_auto(max_batch, row_bytes=row_bytes)
        return _pow2_ceil(tuned if tuned else max_batch)
    n = env_int(SLOTS_ENV, 0)
    if n <= 0:
        n = max_batch
    return _pow2_ceil(n)


class SlotTable:
    """Ping-pong pow2 staging for one serving query's feature rows.

    ``dtype`` is the LANE's staging dtype (``quantize.staging_dtype``):
    a narrow predict lane allocates narrow buffers, so the
    ``aserve_slots`` HBM claim — and the one h2d per dispatch — shrinks
    4x (int8) / 2x (bf16) with no further code. ``quantizer`` is the
    admission transform from ``quantize.row_quantizer`` (None = plain
    cast): raw float rows MUST pass through it on a narrow table, since
    a bare cast of floats to bin-id ``uint8`` would truncate values
    instead of binning them.
    """

    def __init__(self, slots: int, width: int, dtype=np.float32,
                 quantizer=None):
        if slots < 1 or width < 1:
            raise ValueError(f"slot table needs slots>=1 and width>=1, "
                             f"got {slots}x{width}")
        self.slots = _pow2_ceil(slots)
        self.width = int(width)
        self.quantizer = quantizer
        self._bufs = (np.zeros((self.slots, self.width), dtype),
                      np.zeros((self.slots, self.width), dtype))
        self._active = 0
        # HBM-ledger claim: both ping-pong staging buffers, held for the
        # table's lifetime (released via release_claim() at server stop)
        self._claimed = float(sum(b.nbytes for b in self._bufs))
        _hbm.claim("aserve_slots", self._claimed)

    def release_claim(self) -> None:
        """Give the staging buffers' HBM-ledger claim back (idempotent —
        the owning server calls this once at stop)."""
        if self._claimed:
            _hbm.release("aserve_slots", self._claimed)
            self._claimed = 0.0

    @property
    def forming(self) -> np.ndarray:
        """The buffer the loop is currently decoding arrivals into."""
        return self._bufs[self._active]

    def write(self, slot: int, row) -> None:
        """Decode one request's features into ``forming[slot]`` — THE
        admission-time copy (list/JSON -> pinned row), after which the
        row is never touched again until the device upload."""
        if self.quantizer is not None:
            row = self.quantizer(row)
        row = np.asarray(row, dtype=self._bufs[0].dtype)
        if row.shape != (self.width,):
            raise ValueError(f"feature row has shape {row.shape}, "
                             f"expected ({self.width},)")
        self._bufs[self._active][slot, :] = row

    def flip(self) -> np.ndarray:
        """Dispatch: hand the forming buffer to the scoring thread and
        make the other buffer the new forming target."""
        dispatched = self._bufs[self._active]
        self._active ^= 1
        return dispatched

    @staticmethod
    def bucket_view(buf: np.ndarray, n: int) -> Tuple[np.ndarray, int]:
        """``(view, bucket)``: the pow2-bucket slice the compiled
        predictor scores. Padding rows repeat row 0 (the
        ``bucketed_model_transform`` convention) so stale bytes from a
        previous batch can't leak NaN-shaped behavior into the pad."""
        b = bucket_size(n, buf.shape[0])
        if n < b:
            buf[n:b] = buf[0] if n else 0.0
        return buf[:b], b
