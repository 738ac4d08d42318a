"""Percentiles, token gaps and spreads: the arithmetic behind every metric."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def token_gaps(stamps: Iterable[Sequence[float]]) -> List[float]:
    """All gaps between consecutive output tokens, over all requests."""
    gaps: List[float] = []
    for ts in stamps:
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return gaps


def longest_tick_gaps(stamps: Iterable[Sequence[float]], t0: float,
                      n: int = 5, merge_s: float = 0.002
                      ) -> List[List[float]]:
    """The engine's ticks as the callers saw them: stamps at most ``merge_s``
    apart are one tick's. Returns the ``n`` longest gaps between consecutive
    ticks as ``[seconds after t0, gap in ms]``: a stall of the host or the
    device shows here as one long gap, a slower device as none."""
    ts = sorted(t for one in stamps for t in one)
    ticks = [t for i, t in enumerate(ts) if i == 0 or t - ts[i - 1] > merge_s]
    gaps = sorted(((b - a, a) for a, b in zip(ticks, ticks[1:])),
                  reverse=True)[:n]
    return [[round(a - t0, 3), round(1e3 * g, 1)] for g, a in gaps]


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the contract's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
