"""Percentiles, the same way everywhere in the benchmark."""
from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0 < q < 100) by linear interpolation
    between order statistics (``statistics.quantiles``, inclusive)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    if len(vals) == 1:
        return float(vals[0])
    cuts = statistics.quantiles(vals, n=1000, method="inclusive")
    return float(cuts[round(q * 10) - 1])

