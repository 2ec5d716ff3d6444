"""Median and percentile, one rule for every metric.

percentile(values, q): the smallest sample such that at least q % of the
samples are <= it (nearest-rank, no interpolation), so a reported tail is
a latency some request really had.
"""
from __future__ import annotations

import math


def median(values):
    s = sorted(values)
    n = len(s)
    if not n:
        raise ValueError("median of no samples")
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def percentile(values, q: float):
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]
