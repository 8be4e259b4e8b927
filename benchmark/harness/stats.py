"""The benchmark's arithmetic on samples: mean, percentiles, rates and the
spread the bounds are set from. Plain Python, no NumPy, so the numbers
are the same wherever they are computed."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def mean(xs: Sequence[float]) -> Optional[float]:
    return math.fsum(xs) / len(xs) if xs else None


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """q in [0, 100], linear interpolation between order statistics
    (NumPy's default): percentile([1, 2, 3, 4], 50) == 2.5."""
    if not xs:
        return None
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside [0, 100]")
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    """count per second over a window; a window of no length is an error,
    never a rate of 0 or infinity."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


def quartile_spread(xs: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles `statistics.quantiles(xs, n=4)` gives
    (the driver's rule; NumPy's lie closer together)."""
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
