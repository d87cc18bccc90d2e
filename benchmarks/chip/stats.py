"""Arithmetic shared by the metric readers and the bound tools."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile over every value given: the
    smallest value with at least q% of the values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    return float(xs[max(math.ceil(q / 100.0 * len(xs)), 1) - 1])


def spread(values) -> float:
    """Distance between the first and third quartile (Python's
    ``statistics.quantiles``, n=4) as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
