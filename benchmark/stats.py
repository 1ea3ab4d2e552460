"""Percentile and sample-count arithmetic of the benchmark (no JAX).

A timing is reported as a median and the highest percentile that still has
ten samples beyond it (``choosing-metrics`` section 1), with the count.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    closest ranks — numpy's default method, written out so the launcher
    needs no numpy to reduce a report."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def min_samples_for(q: float, beyond: int = 10) -> int:
    """Fewest samples at which ``q`` has ``beyond`` samples beyond it."""
    return int(math.ceil(beyond * 100.0 / (100.0 - q) - 1e-9))


def tail_is_supported(n: int, q: float, beyond: int = 10) -> bool:
    return samples_beyond(n, q) >= beyond
