"""Order statistics for the result line."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it. No interpolation,
    so the number is always one a request really took."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]
