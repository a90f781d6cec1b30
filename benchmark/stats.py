"""The percentile rule, one for every metric."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the order statistics at rank ``q/100 * (n-1)`` — numpy's default
    rule, written out so that the yardstick has no dependency to drift.
    Missing samples are passed as ``math.inf`` and sort last: a
    percentile that lands on one is reported as missing (None)."""
    if not values:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    s = sorted(values)
    rank = q / 100.0 * (len(s) - 1)
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if math.isinf(s[hi]):
        return None
    return s[lo] + (s[hi] - s[lo]) * (rank - lo)
