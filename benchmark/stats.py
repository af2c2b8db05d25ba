"""The benchmark's own arithmetic: percentiles, rates, spreads."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """``q``-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default ``linear`` method), in plain Python."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def whole_step_rate(tokens_per_step: int, steps: int,
                    t_sync_before: float, t_sync_after: float) -> float:
    """Tokens per second over ``steps`` WHOLE steps between two device
    syncs: the one that ended the last warm-up step and the one that
    ended the last measured step. Never steps counted in a fixed time."""
    if steps < 1:
        raise ValueError("no whole step inside the window")
    elapsed = t_sync_after - t_sync_before
    if elapsed <= 0:
        raise ValueError("the closing sync does not follow the opening one")
    return tokens_per_step * steps / elapsed


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, as the driver
    takes it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))
