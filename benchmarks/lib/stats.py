"""Percentiles and spreads over all samples; no trimming, no windows."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Linear-interpolated q-th percentile (0..100) of every sample."""
    if not samples:
        raise ValueError("percentile of no samples")
    xs = sorted(float(s) for s in samples)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``'s quartiles (the
    measure the bounds are set from)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
