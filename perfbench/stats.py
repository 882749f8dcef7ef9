"""Percentiles, rates and spreads: the arithmetic on the harness's clock."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, over ALL values given."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def rate(work: float, t_open: float, t_close: float) -> float:
    """All the work over the whole window."""
    if t_close <= t_open:
        raise ValueError("empty window")
    return work / (t_close - t_open)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the bound is set from it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
