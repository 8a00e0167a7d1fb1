"""Metric arithmetic over a run's records: rates, tails and
inter-token latency.

All times are seconds on the client's clock. The window is
``(t0, t1]``: ``t0`` and ``t1`` are both instants at which the client
observed the engine, so every token counted was produced inside it.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it. None for an empty sample."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[rank - 1])


def rate(count: float, t0: float, t1: float) -> float:
    """Events per second over the window."""
    return count / (t1 - t0)


def itl_samples(records: Iterable) -> List[float]:
    """Inter-token latency of every output token that reached the
    client inside the window, a request's first token excepted: a token
    that came with ``k - 1`` others at one observation took the time
    since the request's previous observation with tokens, over ``k``.
    Each token is one sample, so the tail is the tail of all tokens."""
    out: List[float] = []
    for r in records:
        for secs, k in r.itl:
            out.extend([secs] * k)
    return out
