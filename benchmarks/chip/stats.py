"""Latency arithmetic of the harness: tails over every request of a
window, each timed from the moment it was due."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-quantile (0..1) of ``values``."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    h = (len(v) - 1) * q
    lo = math.floor(h)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (h - lo) * (v[hi] - v[lo])


def ttfts(due: dict, first_token: dict) -> list[float]:
    """Seconds from each request's due time to its first token, for every
    request in ``due``; a request that never produced one counts as
    ``inf``, so it lands in the tail."""
    return [first_token.get(uid, math.inf) - t for uid, t in due.items()]


def token_gaps(token_times: dict, start: float, end: float) -> list[float]:
    """Gaps between consecutive tokens of one request, over all requests,
    for every gap that ends inside ``[start, end)``."""
    out = []
    for times in token_times.values():
        for a, b in zip(times, times[1:]):
            if start <= b < end:
                out.append(b - a)
    return out


def tokens_between(token_times: dict, start: float, end: float) -> int:
    return sum(1 for times in token_times.values() for t in times
               if start <= t < end)
