"""Arithmetic on samples (copied from ``ddlbench_tpu.telemetry.stats`` so the
program cannot move it)."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """q-th percentile (0..100), linear interpolation between order
    statistics (numpy's default). Raises on an empty sample: a tail of
    nothing is not 0."""
    if not samples:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q={q} outside [0, 100]")
    s = sorted(samples)
    k = (len(s) - 1) * (q / 100.0)
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi:
        return s[int(k)]
    return s[lo] + (s[hi] - s[lo]) * (k - lo)
