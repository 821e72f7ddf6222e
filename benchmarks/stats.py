"""Percentile and failure arithmetic of the benchmark (pure Python + numpy)."""
from __future__ import annotations

import statistics
from typing import List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile ``q`` in [0, 100]; None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def with_failures(values: Sequence[float], n_failed: int) -> List[float]:
    """A failed, refused, shed or degraded request misses every limit: it
    enters a latency sample as the largest value observed (so a tail can
    only get worse by it, and the metric stays finite). With no successful
    request there is nothing to be as large as: empty."""
    values = list(values)
    if not values:
        return []
    return values + [max(values)] * int(n_failed)


def gaps(stamps: Sequence[float]) -> List[float]:
    """Gaps between consecutive visible tokens of one request."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def spread(values: Sequence[float]) -> Optional[float]:
    """The driver's spread: distance between the quartiles over the median,
    the quartiles as ``statistics.quantiles(values, n=4)`` gives them
    (numpy's lie closer together: six runs 1.0 .. 1.5 spread by 0.28 here
    and by 0.20 there, and the bounds are set from this number)."""
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / med if med else None
