"""Percentiles by nearest rank, over all the samples given."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """The smallest value with at least q percent of the samples at or
    below it."""
    if not values:
        raise ValueError("no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
