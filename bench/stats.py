"""Order statistics of the benchmark's own samples."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-quantile (0 < q <= 1) of every value: the smallest
    value with at least a share q of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]
