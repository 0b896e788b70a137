"""The program's own spans as the fits they tell of.

The program keeps every span it closes in one in-memory buffer
(``mmlspark_tpu.observability.spans.get_trace_events()``: Chrome trace
events, microseconds on ``time.perf_counter``'s clock). A ``train_booster``
call is one ``gbdt_fit`` span; its phases and jax's compile stages lie inside
it and name their parent. A benchmark process fits once to warm up and then
``attempted`` times in the window, so the first ``gbdt_fit`` of the buffer is
the warm-up fit and the last ``attempted`` are the window's.

A program that records no such spans (a parent commit) gives empty lists
here, and every reader on top gives ``None``.
"""

from __future__ import annotations

FIT = "gbdt_fit"


def events() -> list:
    """The buffer's complete spans, oldest first; ``[]`` where the program
    has no buffer to read."""
    try:
        from mmlspark_tpu.observability import spans
        return [e for e in spans.get_trace_events() if e.get("ph") == "X"]
    except Exception:  # noqa: BLE001 - a program without spans reads as none
        return []


def _within(e: dict, outer: dict) -> bool:
    # a microsecond of slack: a finished span's start is end minus duration
    return (e is not outer and outer["ts"] - 1.0 <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0)


def fits(evs: list) -> list:
    """``[(fit, [the spans inside it])]`` in start order."""
    found = sorted((e for e in evs if e["name"] == FIT),
                   key=lambda e: e["ts"])
    return [(f, [e for e in evs if _within(e, f)]) for f in found]


def warmup_and_window(evs: list, attempted: int) -> tuple:
    """``(warm-up fit, [window fits])``, each a pair as :func:`fits` gives;
    ``(None, [])`` unless the buffer holds the warm-up and every one of the
    window's ``attempted`` fits."""
    all_fits = fits(evs)
    if attempted < 1 or len(all_fits) < attempted + 1:
        return None, []
    return all_fits[0], all_fits[-attempted:]


def of_run(ctx: dict) -> tuple:
    """:func:`warmup_and_window` of this process's buffer, for a reader's
    ``ctx``."""
    return warmup_and_window(events(), ctx["facts"]["attempted"])


def union_s(spans: list):
    """Seconds covered by ``spans`` together, overlaps counted once (a jit
    traced inside another reports its own stage nested in the outer one's);
    ``None`` for no span at all."""
    if not spans:
        return None
    total, end = 0.0, float("-inf")
    for s, e in sorted((x["ts"], x["ts"] + x["dur"]) for x in spans):
        if e > end:
            total += e - max(s, end)
            end = e
    return total * 1e-6


def named(inside: list, *names: str) -> list:
    return [e for e in inside if e["name"] in names]


def tree(members: list, pool: list) -> dict:
    """``members`` (spans of one name under one parent) with what lies under
    them: ``{"s": seconds, "n": count, "attrs": a single member's, "children":
    {name: tree}}``. A child names its parent and lies inside one of them."""
    node = {"s": union_s(members), "n": len(members)}
    attrs = {k: v for k, v in members[0].get("args", {}).items()
             if k != "parent"}
    if attrs and len(members) == 1:
        node["attrs"] = attrs
    name = members[0]["name"]
    below = {}
    for e in pool:
        if e.get("args", {}).get("parent") == name and any(
                _within(e, m) for m in members):
            below.setdefault(e["name"], []).append(e)
    if below:
        node["children"] = {
            n: tree(ms, pool) for n, ms in sorted(below.items(),
                                key=lambda kv: min(e["ts"] for e in kv[1]))}
    return node
