"""Percentiles, censored latencies and rates over one measured window."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_samples(requests: Iterable, t0: float, end: float) -> List[float]:
    """Time to first token of every request due in ``[t0, end)``, from
    its due time.  A request still without a token at ``end`` counts
    with ``end - due``: it waited at least that long."""
    out = []
    for r in requests:
        if not t0 <= r.due < end:
            continue
        first = r.token_times[0] if r.token_times else None
        out.append((first if first is not None and first <= end else end)
                   - r.due)
    return out


def itl_samples(requests: Iterable, t0: float, end: float) -> List[float]:
    """Every gap between consecutive output tokens of a request, where
    both tokens appeared inside ``[t0, end]``."""
    out = []
    for r in requests:
        ts = [t for t in r.token_times if t0 <= t <= end]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in(requests: Iterable, t0: float, end: float) -> int:
    """Output tokens that appeared inside ``[t0, end]``."""
    return sum(1 for r in requests for t in r.token_times if t0 <= t <= end)
