"""Order statistics, one definition for the whole benchmark."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default), of a non-empty sample."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return float(v[lo] + (v[hi] - v[lo]) * (k - lo))


def median(values) -> float:
    return percentile(values, 50.0)
