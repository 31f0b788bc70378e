"""Percentile and spread arithmetic of the benchmark's results."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q`` quantile (0 < q <= 1) by nearest rank: the smallest value
    with at least ``q`` of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile {q} outside (0, 1]")
    xs = sorted(values)
    return xs[max(math.ceil(q * len(xs)) - 1, 0)]


def beyond(values: Sequence[float], q: float) -> int:
    """How many samples lie strictly above the ``q`` nearest-rank value."""
    cut = nearest_rank(values, q)
    return sum(1 for v in values if v > cut)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles`` with ``n=4``, exclusive method)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
