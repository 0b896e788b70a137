"""From a profiler trace to the facts that the per-layer metrics read.

Two steps, kept apart so that the second can be checked on a small recorded
trace (``tests/data``): :func:`load_events` reads the profiler's
``.xplane.pb`` into plain lists, and :func:`reduce_events` turns those into
device busy time, per-operation time, and the longest idle gaps with the
host span that was open in each.

Device operations nest in the trace: a ``while`` spans its body's
operations. Busy time and the per-operation table count only *leaf* events,
those that contain no other event of their line; a container's own time is
the control flow around them and counts as idle.
"""

from __future__ import annotations

import glob
import os

_DEVICE_PLANE = "/device:TPU:"
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # host spans only: TraceAnnotation
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


SPAN_PREFIXES = ("gbdt_", "bench_")


def load_events(log_dir: str, span_prefixes=SPAN_PREFIXES) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]}`` of the newest trace under ``log_dir``: the
    device planes' operation lines, and of the host plane's threads the
    spans whose name starts with one of ``span_prefixes`` (the program's
    ``TraceAnnotation``s and the benchmark's own)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(_DEVICE_PLANE)
        if not device and plane.name != _HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != _OPS_LINE:
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(tuple(span_prefixes))]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _leaves(events):
    """Events that contain no other event of the same line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is not None and nxt[1] < start + dur and \
                nxt[1] + nxt[2] <= start + dur + 1e-6:
            continue                               # it has a child
        out.append((name, start, dur))
    return out


def _union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_spans(doc):
    return [(n, s, s + d) for plane in doc["planes"]
            if plane["name"] == _HOST_PLANE
            for line in plane["lines"] for n, s, d in line["events"]]


def reduce_events(doc: dict) -> dict:
    """Facts of one traced window:

    ``busy_s``: seconds in which a leaf operation ran, averaged over the
    device planes; ``span_s``: first operation's start to the last one's
    end, likewise; ``ops``: ``{name: [seconds, launches]}`` of leaf
    operations summed over the devices; ``idle_gaps``: the ten longest gaps
    between operations on the first device, each named by the innermost host
    span open at its middle."""
    devices = [p for p in doc["planes"]
               if p["name"].startswith(_DEVICE_PLANE)]
    ops, busy, span, first_gaps = {}, [], [], None
    for plane in sorted(devices, key=lambda p: p["name"]):
        leaves = [e for line in plane["lines"]
                  for e in _leaves(line["events"])]
        if not leaves:
            continue
        for name, _, dur in leaves:
            slot = ops.setdefault(name, [0.0, 0])
            slot[0] += dur * 1e-9
            slot[1] += 1
        merged = _union((s, s + d) for _, s, d in leaves)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        span.append((merged[-1][1] - merged[0][0]) * 1e-9)
        if first_gaps is None:
            first_gaps = [(b[0] - a[1], a[1], b[0])
                          for a, b in zip(merged, merged[1:])]
    if not busy:
        return {"busy_s": 0.0, "span_s": 0.0, "ops": {}, "idle_gaps": [],
                "devices": 0}
    spans = _host_spans(doc)
    gaps = []
    for length, start, end in sorted(first_gaps or [], reverse=True)[:10]:
        mid = (start + end) / 2
        open_ = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        gaps.append([min(open_)[1] if open_ else "no host span open",
                     length * 1e-9])
    return {"busy_s": sum(busy) / len(busy), "span_s": sum(span) / len(span),
            "ops": ops, "idle_gaps": gaps, "devices": len(busy)}


def top_ops(ops: dict, k: int = 10):
    """``[[name, seconds], ...]`` of the ``k`` operations with most time."""
    return [[n, v[0]] for n, v in sorted(
        ops.items(), key=lambda kv: -kv[1][0])[:k]]


def mosaic_kernels(ops: dict, rows: int):
    """(seconds, launches) of the Mosaic kernels that stream a matrix of
    ``rows`` columns: the program's two ``pallas_call``s carry no ``name=``,
    so the match is the name Mosaic gives every kernel,
    ``custom_call_target="tpu_custom_call"``, with the binned matrix
    ``[F, rows]`` as first operand. ``(0.0, 0)`` where none ran."""
    import re
    first_operand = re.compile(r"custom-call\(\w+\[\d+," + str(int(rows))
                               + r"\]")
    seconds, launches = 0.0, 0
    for name, (s, c) in ops.items():
        if 'custom_call_target="tpu_custom_call"' in name and \
                first_operand.search(name):
            seconds += s
            launches += c
    return seconds, launches
