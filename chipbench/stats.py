"""Percentiles for the benchmark's latency metrics: linear interpolation
between closest ranks, numpy's default method. A copy kept with the
benchmark so that no change to the program can change how a metric is
computed."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile needs at least one value")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    k = (len(xs) - 1) * (q / 100.0)
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)
