"""Percentiles and spreads, as the contract defines them."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all values (q in 0..100)."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    k = max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))
    return float(s[k])


def iqr_share(values) -> float:
    """Distance between first and third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
