"""The window's arithmetic: rates and percentiles. A rate is all the
work over all the time; a percentile is over all calls of the window."""

from __future__ import annotations

import math


def rate(work: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"rate over {seconds} s")
    return work / seconds


def percentile(samples, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the
    smallest sample with at least q % of all samples at or below it. No
    interpolation, so the value is always one that was measured."""
    if not samples:
        raise ValueError("percentile of no samples")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q}")
    s = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def median(samples) -> float:
    return percentile(samples, 50)


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(intervals, lo: float, hi: float):
    """The idle gaps (start, end) inside [lo, hi] that no interval
    covers."""
    out, cursor = [], lo
    for a, b in sorted(intervals):
        if a > cursor:
            out.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]
