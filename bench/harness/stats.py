"""Order statistics the benchmark reports, defined once."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank q-th percentile (0 < q <= 100): the smallest value
    with at least q% of the sample at or below it. Infinite values (a
    failed request) count, and can be the answer. None when empty."""
    xs: List[float] = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def median(values: Iterable[float]) -> Optional[float]:
    xs = list(values)
    return statistics.median(xs) if xs else None
