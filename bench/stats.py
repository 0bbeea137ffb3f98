"""Percentiles: the benchmark's own arithmetic.

``percentile`` is the nearest-rank rule of ``repro.core.metrics``,
copied here so that no change to the program can move the yardstick.
"""
from __future__ import annotations

import math


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (a request that never got
    its token) rank above every finite one."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of no samples")
    return s[min(len(s) - 1, max(0, int(math.ceil(q * len(s))) - 1))]
