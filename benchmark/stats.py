"""Metric arithmetic on raw samples, with the sample count always stated."""
from __future__ import annotations

import math
import statistics


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile (0 < q < 100) of raw ``samples`` by linear
    interpolation between order statistics (numpy's default rule, written
    out so that the yardstick does not move with a library)."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def summary(samples, scale: float = 1.0) -> dict:
    """Count, median, p95 and maximum of ``samples`` times ``scale``: what
    goes on an earlier line beside every tail that is a metric."""
    if not samples:
        return {"n": 0}
    return {"n": len(samples),
            "median": statistics.median(samples) * scale,
            "p95": percentile(samples, 95) * scale,
            "max": max(samples) * scale}
