"""Exact order statistics and whole-window rates, from raw samples.

No bucketing and no averaging of chunks: a percentile is read off the
sorted samples by linear interpolation between the two nearest ranks
(numpy's default ``linear`` method), and a rate is everything counted in
the window over the window's length.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(samples: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) of ``samples``; None when empty.

    >>> percentile([1, 2, 3, 4], 50)
    2.5
    >>> percentile([5.0], 95)
    5.0
    """
    xs = sorted(float(x) for x in samples)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q={q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, t0: float, t1: float) -> float:
    """``count`` over the window ``[t0, t1)`` in seconds."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1})")
    return count / (t1 - t0)


def count_in(times: Sequence[float], t0: float, t1: float) -> int:
    """How many timestamps fall in ``[t0, t1)``."""
    return sum(1 for t in times if t0 <= t < t1)


def union_length(intervals: Iterable[tuple], lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    total = 0.0
    cur_s = cur_e = None
    for s, e in clipped:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[tuple], lo: float, hi: float) -> list:
    """The ``(start, end)`` stretches of ``[lo, hi]`` that no interval
    covers, in time order."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]
