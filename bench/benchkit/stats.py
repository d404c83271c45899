"""Percentile and rate arithmetic over every token and every request of a
window.  Nothing is sampled or smoothed: a tail is the tail of all."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float | None:
    """Nearest-rank ``q``-th percentile (0 < q <= 100); None when empty."""
    if not values:
        return None
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def window_tokens(stamps: Sequence[Sequence[float]], t_end: float) -> int:
    """Tokens emitted up to ``t_end``, over every request's emit stamps."""
    return sum(sum(1 for t in s if t <= t_end) for s in stamps)


def inter_token_gaps(stamps: Sequence[Sequence[float]],
                     t_end: float) -> list[float]:
    """Seconds since the same request's previous token, for every token
    after a request's first that was emitted up to ``t_end``."""
    gaps: list[float] = []
    for s in stamps:
        for a, b in zip(s, s[1:]):
            if b > t_end:
                break
            gaps.append(b - a)
    return gaps
