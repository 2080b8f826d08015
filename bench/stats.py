"""Percentiles, rates and spreads, one definition for every metric."""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100), nearest rank: the smallest value
    with at least ``q`` percent of the sample at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
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


def segment_rates(events: Iterable[Tuple[float, float]], t0: float,
                  t1: float, n: int = 5) -> List[float]:
    """Per second of each of ``n`` equal parts of ``[t0, t1]``: the sum
    of the counts of ``(time, count)`` events that fall in it.  Shows
    where in a window a run lost its pace."""
    width = (t1 - t0) / n
    tot = [0.0] * n
    for t, c in events:
        if t0 <= t <= t1:
            tot[min(int((t - t0) / width), n - 1)] += c
    return [x / width for x in tot]
