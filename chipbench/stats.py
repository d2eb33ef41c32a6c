"""The benchmark's arithmetic on samples: every rate is all the work over all
the time of the window, and every percentile is over every sample, so a
stall anywhere in the window moves them (no median of chunks)."""
from __future__ import annotations

import math


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile (0..100) of all samples, linearly
    interpolated between order statistics (numpy's default method)."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def rate(work: float, seconds: float) -> float:
    """Work per second over a window that must have a length."""
    if seconds <= 0:
        raise ValueError(f"a rate needs a window, got {seconds} s")
    return work / seconds


def token_gaps(events):
    """Inter-token gaps from ``(time_s, request_id)`` token events in time
    order: for every token after a request's first, the wall time since
    that request's previous token."""
    last: dict = {}
    gaps = []
    for t, rid in events:
        if rid in last:
            gaps.append(t - last[rid])
        last[rid] = t
    return gaps
