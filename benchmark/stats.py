"""The arithmetic of the serving metrics.  Pure Python: the load generator's
child imports it, and the tests check it on hand-made numbers."""

from __future__ import annotations

import math
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule on the sorted
    values: the smallest value with at least q % of the values at or below
    it.  No interpolation: a tail is a latency some request really had."""
    if not values:
        return float("nan")
    v = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return v[min(k, len(v)) - 1]


def request_latencies_ms(requests: List[dict], window_s: float) -> List[float]:
    """Per request: response fully read - time it was *due* (so the wait a
    stall imposes on later requests counts); a failed or refused request
    counts as the window's length."""
    out = []
    for r in requests:
        ok = r["status"] == 200 and r["scores_ok"]
        out.append((r["done"] - r["due"]) * 1000.0 if ok else window_s * 1000.0)
    return out


def goodput(requests: List[dict], window_s: float, limit_ms: float) -> float:
    """Records in requests answered 200 with finite scores within
    ``limit_ms`` of when due, per second of window."""
    good = sum(r["records"] for r in requests
               if r["status"] == 200 and r["scores_ok"]
               and (r["done"] - r["due"]) * 1000.0 <= limit_ms)
    return good / window_s


def lateness_ms(requests: List[dict]) -> List[float]:
    """How late the generator sent each request: sent - due."""
    return [(r["sent"] - r["due"]) * 1000.0 for r in requests]


def backlog(requests: List[dict], at: float) -> int:
    """Requests due by ``at`` and not yet answered at ``at``."""
    return sum(1 for r in requests if r["due"] <= at and r["done"] > at)
