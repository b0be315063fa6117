"""Order statistics used by the metric readers."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it.  ``inf`` entries (a request
    that never finished) sort last; an empty list gives ``nan``."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return float("nan")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]
