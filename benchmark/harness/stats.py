"""Arithmetic on lists of readings. Plain Python, no dependency on how
the readings were taken."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as ``numpy.percentile``'s default; None for no
    values."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def periods_late(latencies_ms: Sequence[float], period_ms: float) -> list[int]:
    """Whole window periods each result left after its last event."""
    return [int(l // period_ms) for l in latencies_ms]


def latency_excess(latencies_ms: Sequence[float], period_ms: float) -> list[float]:
    """What is left of each latency once the whole periods are taken out."""
    return [l - (l // period_ms) * period_ms for l in latencies_ms]


def spread(values: Sequence[float]) -> Optional[float]:
    """Interquartile distance as a share of the median, by the quartiles of
    ``statistics.quantiles(values, n=4)`` — the contract's spread."""
    import statistics

    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def phase_of(at: float, origin: float, period: float) -> float:
    """Where in its slide an instant falls, as a share in [0, 1): boundary
    ``b`` of a paced stream is due at ``origin + b * period``."""
    x = ((at - origin) / period) % 1.0
    return 0.0 if x > 1.0 - 1e-6 else x


def struck(closes: Sequence[tuple[float, float]],
           barriers: Sequence[tuple[float, Optional[float]]]) -> list[bool]:
    """For each close ``(due, arrived)``: did a barrier ``(triggered,
    durable)`` overlap it. A barrier that never became durable overlaps
    everything after its trigger."""
    return [any(t <= arrived and (d is None or d >= due) for t, d in barriers)
            for due, arrived in closes]


def histogram_quantile(bounds: Sequence[float], counts: Sequence[int],
                       q: float) -> Optional[float]:
    """Upper bound of the bucket holding the ``q`` quantile (0..1) of a
    fixed-bucket histogram whose last count is the overflow bucket; the
    overflow reads as the largest finite bound."""
    total = sum(counts)
    if not total:
        return None
    rank, seen = q * total, 0
    for i, c in enumerate(counts):
        seen += c
        if c and seen >= rank:
            return float(bounds[min(i, len(bounds) - 1)])
    return float(bounds[-1])
