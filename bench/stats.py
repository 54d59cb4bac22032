"""Percentiles and the per-request latencies they are taken over."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Linear interpolation between order statistics (numpy's default);
    an infinite value (a request that never answered) stays infinite."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = p / 100 * (len(xs) - 1)
    lo, hi = math.floor(rank), math.ceil(rank)
    if math.isinf(xs[hi]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def ttfts_s(window):
    """Due time to first token of every request due in the window;
    infinite for one that had none by the drain cap."""
    return [(t.token_times[0] - t.due) if t.token_times else math.inf
            for t in window.tracks.values()]


def itl_s(window):
    """Every gap between successive tokens of every request due in the
    window."""
    return [b - a for t in window.tracks.values()
            for a, b in zip(t.token_times, t.token_times[1:])]
