"""Rates and spreads, as the benchmark computes them."""

from __future__ import annotations

import statistics
from typing import Optional, Sequence


def rate(count: float, seconds: float) -> Optional[float]:
    """``count`` per second over a window of ``seconds``; None if empty."""
    if seconds <= 0 or count <= 0:
        return None
    return count / seconds


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(q2)
