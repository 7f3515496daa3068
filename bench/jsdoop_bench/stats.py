"""Order statistics as the benchmark reports them."""
from __future__ import annotations

import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0 < q < 100) of all ``values``, linearly
    interpolated between order statistics; one value is its own
    percentile."""
    if not values:
        raise ValueError("percentile of no values")
    if len(values) == 1:
        return float(values[0])
    cuts = statistics.quantiles(values, n=1000, method="inclusive")
    return float(cuts[int(round(q * 10)) - 1])
