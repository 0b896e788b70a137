#!/usr/bin/env python
"""Round-over-round bench regression gate.

Gates the newest ``BENCH_r*.json`` file in the repo root (or the
directory given as the first argument) against the MEDIAN of up to the
three rounds preceding it: each file is a driver wrapper object whose
``tail`` holds the bench run's stdout, where the LAST JSON line is the
round's metrics (bench.py's last-line-wins convention; a bare JSON-line
file is accepted too). A single-round baseline is one noisy sample away
from a false flag; the median of a short window absorbs one outlier round
in either direction. On an even window the LOWER middle
value is taken — ties break toward not flagging. Throughput keys shared
by the baseline and the newest round — ``value`` (when every baseline
round and the newest report the same ``metric`` name) and every
``*_per_sec`` / ``*_rps`` key — must not drop more than the threshold
(default 20%). Keys that are missing, non-numeric, or <= 0 in a round
(failed secondaries report -1) are skipped in that round.

Exit status: 0 = no regression (or fewer than two rounds to compare),
1 = at least one key regressed, 2 = usage/parse error. Wired as a fast
test in ``tests/test_tools.py`` on synthetic fixtures; run it by hand
after a bench round::

    python tools/bench_regression.py            # repo root
    python tools/bench_regression.py --threshold 0.1 /path/to/rounds
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_ROUND_RE = re.compile(r"^BENCH_r(\d+)\.json$")
#: throughput keys: higher is better, eligible for the regression gate
_RATE_RE = re.compile(r".*(_per_sec|_rps)$")


def _bench_line(path: str) -> Optional[Dict]:
    """The round's metrics dict: last parseable JSON object line of the
    wrapper's ``tail`` (or of the raw file)."""
    try:
        with open(path, encoding="utf-8") as f:
            raw = f.read()
    except OSError as e:
        print(f"bench_regression: cannot read {path}: {e}", file=sys.stderr)
        return None
    text = raw
    try:
        obj = json.loads(raw)
        if isinstance(obj, dict) and "metric" in obj:
            return obj                      # already a bare bench line
        if isinstance(obj, dict) and isinstance(obj.get("tail"), str):
            text = obj["tail"]
    except json.JSONDecodeError:
        pass                                # treat the file as line-oriented
    last = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            last = parsed
    return last


def _rounds(directory: str) -> List[Tuple[int, str]]:
    out = []
    try:
        names = os.listdir(directory)
    except OSError as e:
        print(f"bench_regression: cannot list {directory}: {e}",
              file=sys.stderr)
        return out
    for name in names:
        m = _ROUND_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    return sorted(out)


def _comparable_keys(prev: Dict, cur: Dict) -> List[str]:
    keys = [k for k in cur
            if _RATE_RE.match(k) and k in prev]
    # the headline "value" compares only when both rounds measured the
    # same, explicitly named metric (a TPU round must not be gated
    # against a CPU fallback, and a round that lost its "metric" key
    # must not be gated against anything)
    if "metric" in prev and "metric" in cur \
            and prev["metric"] == cur["metric"] \
            and "value" in prev and "value" in cur:
        keys.append("value")
    return sorted(set(keys))


def _low_median(xs: List[float]) -> float:
    """Median taking the LOWER middle value on even windows — with two
    baseline rounds a tie breaks toward the slower one, so one fast
    outlier round cannot manufacture a regression flag."""
    xs = sorted(xs)
    return xs[(len(xs) - 1) // 2]


def baseline(rounds: List[Dict]) -> Dict:
    """Fold a window of previous rounds into one synthetic baseline:
    per shared throughput key, the low-median of the rounds that report
    a usable (numeric, > 0) value. ``metric``/``value`` participate only
    when EVERY window round names the same metric — a window mixing a
    TPU round with a CPU fallback must not gate the headline at all."""
    out: Dict = {}
    keys = set()
    for r in rounds:
        keys.update(k for k in r if _RATE_RE.match(k))
    for key in keys:
        vals = []
        for r in rounds:
            try:
                v = float(r[key])
            except (KeyError, TypeError, ValueError):
                continue
            if v > 0:
                vals.append(v)
        if vals:
            out[key] = _low_median(vals)
    metrics = {r.get("metric") for r in rounds}
    if len(metrics) == 1 and None not in metrics:
        vals = []
        for r in rounds:
            try:
                v = float(r["value"])
            except (KeyError, TypeError, ValueError):
                continue
            if v > 0:
                vals.append(v)
        if vals:
            out["metric"] = metrics.pop()
            out["value"] = _low_median(vals)
    return out


def compare(prev: Dict, cur: Dict, threshold: float) -> List[str]:
    """Human-readable regression lines (empty = pass)."""
    out = []
    for key in _comparable_keys(prev, cur):
        try:
            old, new = float(prev[key]), float(cur[key])
        except (TypeError, ValueError):
            # non-numeric value (wrapper noise) — skip, never crash.
            # Keys missing from either round never reach here:
            # _comparable_keys only returns keys present in both.
            continue
        if old <= 0 or new <= 0:
            continue                      # -1 sentinel / failed secondary
        drop = (old - new) / old
        if drop > threshold:
            out.append(f"{key}: {old:g} -> {new:g} "
                       f"({drop * 100:.1f}% drop > {threshold * 100:.0f}%)")
    return out


def roofline_lines(prev_rounds: List[Dict], cur: Dict) -> List[str]:
    """Report-only ``*_roofline_pct`` trend lines (measured %-of-peak
    from bench.py's roofline epilogue). NEVER part of the gate: percent
    of hardware peak is a diagnosis axis, not a throughput contract —
    the keys deliberately fail ``_RATE_RE`` so they cannot leak into
    ``compare()``/``baseline()`` even by accident."""
    keys = sorted(k for k in cur
                  if k.endswith("_roofline_pct") and not _RATE_RE.match(k))
    out = []
    for key in keys:
        try:
            new = float(cur[key])
        except (TypeError, ValueError):
            continue
        olds = []
        for r in prev_rounds:
            try:
                olds.append(float(r[key]))
            except (KeyError, TypeError, ValueError):
                continue
        if olds:
            old = _low_median(olds)
            out.append(f"{key}: {old:g}% -> {new:g}% (report-only)")
        else:
            out.append(f"{key}: {new:g}% (report-only, no baseline)")
    return out


def tuning_lines(prev_rounds: List[Dict], cur: Dict) -> List[str]:
    """Report-only auto-tuner provenance diff. bench.py stamps the round
    line with ``"tuning": {"status": ..., "<site>": <choice>, ...}``
    when a tuning store is configured (absent/None otherwise). NEVER
    part of the gate: a flipped knob is attribution for a throughput
    move, not a regression by itself — a round that regressed AND
    flipped a knob reads "the tuner moved" before "the code got
    slower"."""
    cur_t = cur.get("tuning")
    if not isinstance(cur_t, dict):
        return []
    prev_t = None
    for r in reversed(prev_rounds):  # newest baseline with a stamp wins
        if isinstance(r.get("tuning"), dict):
            prev_t = r["tuning"]
            break
    if prev_t is None:
        return [f"tuning: {json.dumps(cur_t, sort_keys=True)} "
                "(report-only, no baseline provenance)"]
    out = []
    for key in sorted(set(prev_t) | set(cur_t)):
        old, new = prev_t.get(key), cur_t.get(key)
        if old != new:
            out.append(f"tuning[{key}]: {old!r} -> {new!r} (report-only)")
    if not out:
        out.append("tuning: provenance unchanged vs baseline (report-only)")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="bench_regression")
    p.add_argument("directory", nargs="?",
                   default=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))),
                   help="directory holding BENCH_r*.json (default: repo root)")
    p.add_argument("--threshold", type=float, default=0.2,
                   help="max allowed fractional drop (default 0.2 = 20%%)")
    p.add_argument("--window", type=int, default=3,
                   help="baseline rounds preceding the newest to take "
                        "the median over (default 3)")
    args = p.parse_args(argv)
    if args.window < 1:
        print("bench_regression: --window must be >= 1", file=sys.stderr)
        return 2

    rounds = _rounds(args.directory)
    if len(rounds) < 2:
        print(f"bench_regression: {len(rounds)} round(s) in "
              f"{args.directory}; nothing to compare")
        return 0
    (n_cur, p_cur) = rounds[-1]
    cur = _bench_line(p_cur)
    if cur is None:
        print(f"bench_regression: no parseable bench line in {p_cur}",
              file=sys.stderr)
        return 2
    window = rounds[-1 - args.window:-1]
    prev_lines, prev_names = [], []
    for n_prev, p_prev in window:
        line = _bench_line(p_prev)
        if line is None:
            # an unparseable baseline round shrinks the window rather
            # than failing the gate — the newest round is what's judged
            print(f"bench_regression: skipping unparseable baseline "
                  f"{p_prev}", file=sys.stderr)
            continue
        prev_lines.append(line)
        prev_names.append(f"r{n_prev:02d}")
    if not prev_lines:
        print(f"bench_regression: no parseable baseline among "
              f"{[p for _, p in window]}", file=sys.stderr)
        return 2
    prev = baseline(prev_lines)
    label = f"median({','.join(prev_names)})" if len(prev_names) > 1 \
        else prev_names[0]
    regressions = compare(prev, cur, args.threshold)
    trends = roofline_lines(prev_lines, cur) + tuning_lines(prev_lines, cur)
    if regressions:
        print(f"bench_regression: r{n_cur:02d} regressed vs {label}:")
        for line in regressions:
            print(f"  {line}")
        for line in trends:
            print(f"  {line}")
        return 1
    keys = _comparable_keys(prev, cur)
    print(f"bench_regression: r{n_cur:02d} vs {label} OK "
          f"({len(keys)} shared throughput keys within "
          f"{args.threshold * 100:.0f}%)")
    for line in trends:
        print(f"  {line}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
