"""Arithmetic on the window's request log: percentiles, rates, shares.
Plain Python on plain numbers, so that a hand-made log checks it."""

from __future__ import annotations

import math


def percentile(values: list, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def highest_supported_percentile(n: int, beyond: int = 10) -> float:
    """The highest percentile with at least `beyond` samples above it."""
    if n <= beyond:
        return 0.0
    return 100.0 * (n - beyond) / n


def in_window(log: list, t0: float, t1: float) -> list:
    """The turns that finished inside [t0, t1]."""
    return [r for r in log if t0 <= r["t_done"] <= t1]


def tokens_inside(log: list, t0: float, t1: float) -> float:
    """Completion tokens of the work done inside [t0, t1]. A turn that
    straddles an edge counts by the share of its time, submit to result,
    that lies inside: the part of a turn served before the window opened
    is not the window's work, and the part of an unfinished one served
    before it closed is. (Counting whole turns by where they finished
    credits the first and drops the second; the two cancel only on average,
    and were most of the seed-to-seed spread of the rate, PERF.md
    section 2.) A turn in flight when the window closes is waited for."""
    total = 0.0
    for r in log:
        if not r["ok"] or "t_submit" not in r:
            continue
        span = r["t_done"] - r["t_submit"]
        inside = min(r["t_done"], t1) - max(r["t_submit"], t0)
        if span > 0 and inside > 0:
            total += r["completion_tokens"] * inside / span
    return total


def end_to_end(log: list, t0: float, t1: float) -> dict:
    """Latency percentiles over the window's completed turns and tokens per
    second over the WHOLE window; failed turns have no latency sample."""
    rows = in_window(log, t0, t1)
    ok = [r for r in rows if r["ok"]]
    lat = [r["latency_ms"] for r in ok]
    out = {"attempted": len(rows), "failed": len(rows) - len(ok),
           "samples": len(lat)}
    if lat:
        out["turn_latency_p50_ms"] = percentile(lat, 50)
        out["turn_latency_p95_ms"] = percentile(lat, 95)
        hp = highest_supported_percentile(len(lat))
        out["highest_supported_percentile"] = hp
        out["latency_at_highest_supported_ms"] = percentile(lat, hp)
    out["output_tokens_per_s"] = tokens_inside(log, t0, t1) / (t1 - t0)
    out["finished_turns_tokens_per_s"] = (
        sum(r["completion_tokens"] for r in ok) / (t1 - t0))
    return out
