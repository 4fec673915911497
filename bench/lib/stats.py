"""Percentiles of the harness (copied from ``benchmarks/loadgen.py``)."""

from __future__ import annotations

import math


def nearest_rank(samples, p: float) -> float:
    """Nearest-rank percentile: the smallest sample such that at least
    ``p`` percent of the samples are <= it (rank ``ceil(p/100 * n)``).

    >>> nearest_rank([10.0, 20.0, 30.0, 40.0], 50)
    20.0
    >>> nearest_rank([10.0, 20.0, 30.0, 40.0], 95)
    40.0
    """
    if not 0 <= p <= 100:
        raise ValueError(f"percentile must be in [0, 100], got {p}")
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("no samples")
    return xs[max(1, math.ceil(p / 100.0 * len(xs))) - 1]
