"""The arithmetic behind the end-to-end numbers: rates over whole units
between two stamps, and percentiles that know how many samples they had."""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple


def whole_unit_rate(
    stamps: Sequence[float], units_each: float
) -> Optional[Tuple[float, int, float]]:
    """`stamps` are host times taken after the device was blocked on, one
    at the end of each whole unit of work (the first closes the lead-in).
    The rate is the units completed after the first stamp over the time
    that really passed between it and the last: never a part of a
    unit, never the time the run was asked for. Returns (rate, whole
    units, elapsed seconds), or None with fewer than two stamps."""
    stamps = list(stamps)
    if len(stamps) < 2:
        return None
    elapsed = stamps[-1] - stamps[0]
    if elapsed <= 0:
        return None
    n = len(stamps) - 1
    return n * units_each / elapsed, n, elapsed


def completion_rate(
    finishes: Sequence[Tuple[float, float]],
    lo: float,
    hi: float,
    same_event_s: float = 0.002,
) -> Optional[Tuple[float, int, float]]:
    """Throughput from whole requests. `finishes` is (finish stamp, units)
    per finished request. The clock starts at the first completion at or
    after `lo` and stops at the last one before `hi`; the units counted
    are those of completions after the first event (completions within
    `same_event_s` of the first are the same scheduler step, and their
    work was done before the clock started). Returns (rate, requests
    counted, elapsed) or None."""
    inside = sorted((t, u) for t, u in finishes if lo <= t < hi)
    if len(inside) < 2:
        return None
    t_first = inside[0][0]
    t_last = inside[-1][0]
    counted = [(t, u) for t, u in inside if t > t_first + same_event_s]
    elapsed = t_last - t_first
    if not counted or elapsed <= 0:
        return None
    return sum(u for _, u in counted) / elapsed, len(counted), elapsed


def percentile(values: Sequence[float], p: float, beyond: int = 10):
    """The p-th percentile (nearest rank) of `values`, or None when fewer
    than `beyond` samples lie beyond it: a tail with nine samples behind
    it is one request's luck."""
    xs = sorted(values)
    n = len(xs)
    if n == 0:
        return None
    rank = max(1, math.ceil(p / 100.0 * n))
    if p > 50 and n - rank < beyond:
        return None
    return xs[rank - 1]


def median(values: Sequence[float]) -> Optional[float]:
    xs = sorted(values)
    if not xs:
        return None
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])
