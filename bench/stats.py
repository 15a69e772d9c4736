"""Percentiles, rates and spreads, as the benchmark computes them."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics.

    A request that never answered is in ``values`` as ``math.inf``: it
    stays in the sample and pushes the tail up; it is never dropped."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[hi] == math.inf:
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("a rate needs a positive window")
    return count / seconds


def spread(values) -> float:
    """Inter-quartile distance as a share of the median."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def gaps_within(times: list[float], t0: float, t1: float) -> list[float]:
    """Gaps between consecutive timestamps that both lie in (t0, t1]."""
    inside = [t for t in times if t0 < t <= t1]
    return [b - a for a, b in zip(inside, inside[1:])]
