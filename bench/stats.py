"""Order statistics shared by the metric readers."""
from __future__ import annotations

import math


def percentile(xs, q: float):
    """Nearest rank: the smallest sample with at least ``q`` of the data
    at or below it. None for no samples."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[max(1, min(math.ceil(q * len(xs)), len(xs))) - 1]
