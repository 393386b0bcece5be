"""Arithmetic of the end-to-end metrics. Pure functions, no JAX.

Kept here, under the benchmark's own directory, so that no PR that claims
a gain can change how a number is computed.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default), on a plain list."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def slope(times: Sequence[float], amounts: Sequence[float]) -> float:
    """Least-squares slope of the cumulative amount against time.

    `times[i]` is when `amounts[i]` units were credited. A count over a
    window moves by a whole credit at each edge (one 512-960-token prompt is
    0.4-0.8% of a 40 s window at 3k tokens/s); the slope of the staircase
    does not, because an event that slips across an edge moves the fit by
    its share of the points, not by its size.
    """
    if len(times) != len(amounts):
        raise ValueError("times and amounts differ in length")
    if len(times) < 2:
        raise ValueError("a slope needs at least two events")
    order = sorted(range(len(times)), key=times.__getitem__)
    ts, cum, total = [], [], 0.0
    for i in order:
        total += amounts[i]
        ts.append(times[i])
        cum.append(total)
    n = len(ts)
    mt = sum(ts) / n
    mc = sum(cum) / n
    sxx = sum((t - mt) ** 2 for t in ts)
    if sxx == 0.0:
        raise ValueError("all events at one instant")
    return sum((t - mt) * (c - mc) for t, c in zip(ts, cum)) / sxx


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as the driver takes it
    (`statistics.quantiles(values, n=4)`)."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
