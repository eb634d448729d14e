"""Arithmetic of the end-to-end metrics and of their spread.

``percentile`` is the exact sample percentile with linear interpolation
between order statistics (numpy's default rule), over every sample: no
streaming estimate.  ``spread`` is the distance between the first and the
third quartile as ``statistics.quantiles(values, n=4)`` gives them, as a
share of the median: the measure a bound is set from.
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values``."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: int, seconds: float) -> float:
    """Events per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
