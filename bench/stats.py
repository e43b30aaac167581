"""Tail and rate arithmetic for the end-to-end metrics.

A request that failed, was shed or never finished is a miss: it enters
a tail as ``inf``, so it can only push the percentile up (the gateway's
own ``percentile`` drops failures, which flatters a tail). Percentiles
are nearest-rank, so a tail is always a latency that some request
really had, or ``inf``.
"""

from __future__ import annotations

import math
from typing import Iterable, Optional

MISS = math.inf
# what a tail that lands on a miss reads in a result line (JSON has no
# infinity); no latency of a run comes near it
MISS_READING = 1e9


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (0 < q <= 100); None when empty.
    ``inf`` entries (misses) sort last."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def reading(value: Optional[float]) -> Optional[float]:
    """A tail as printed: ``inf`` becomes ``MISS_READING``."""
    if value is None:
        return None
    return MISS_READING if math.isinf(value) else value


def latency(start: float, end: Optional[float], ok: bool = True) -> float:
    """``end - start``, or a miss when the work failed or never ended."""
    if not ok or end is None:
        return MISS
    return end - start


def rate(amounts_at: Iterable[tuple], t0: float, t1: float) -> float:
    """Sum of the amounts whose time lies in ``[t0, t1)``, per second of
    that window. ``amounts_at``: ``(time, amount)`` pairs."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1})")
    return sum(a for t, a in amounts_at if t0 <= t < t1) / (t1 - t0)

