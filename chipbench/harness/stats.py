"""The benchmark's own arithmetic on samples: medians, the percentile rule and
the spread the bounds are set from."""

import math
import statistics
from typing import Optional, Sequence

#: a percentile is reported only with this many samples beyond it
MIN_BEYOND = 10


def median(values: Sequence[float]) -> Optional[float]:
    values = list(values)
    return float(statistics.median(values)) if values else None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0 < q < 100) by nearest rank: the smallest
    sample with at least ``q`` percent of the samples at or below it.  Nothing
    unless at least ``MIN_BEYOND`` samples lie beyond it: a p95 wants 200."""
    values = sorted(values)
    n = len(values)
    if not n:
        return None
    rank = math.ceil(q / 100.0 * n)            # 1-based
    if n - rank < MIN_BEYOND:
        return None
    return float(values[rank - 1])


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the spread the contract's bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
