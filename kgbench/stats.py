"""Percentiles as the benchmark computes them."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at
    least q% of the values at or below it. Infinite values (failed
    requests) sort last; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])
