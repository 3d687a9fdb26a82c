"""Order statistics and due-time arithmetic; checked in tests/test_stats.py."""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The `q`-th percentile (0..100) by linear interpolation between the
    two nearest order statistics, as numpy's default does."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def request_latencies_ms(
    due_s: Sequence[float],
    done_s: Sequence[Optional[float]],
    failed: Sequence[bool],
    fail_ms: float,
) -> List[float]:
    """Latency of each request from the instant it was DUE (not sent) to
    its last verdict frame. A request that failed, was shed or got no
    answer (`done_s` None) reads `fail_ms`, a value over any limit."""
    out = []
    for due, done, bad in zip(due_s, done_s, failed, strict=True):
        if bad or done is None:
            out.append(fail_ms)
        else:
            out.append((done - due) * 1000.0)
    return out


def stratified_gaps(n: int, rate: float) -> List[float]:
    """`n` inter-arrival gaps of a Poisson process of `rate` a second, as
    the fixed set of exponential quantiles: every seed permutes the same
    gaps, so every seed offers the same load over the same span."""
    if n <= 0 or rate <= 0:
        raise ValueError("need n > 0 and rate > 0")
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate * sum(raw))  # the span is exactly n / rate seconds
    return [g * scale for g in raw]


def quota(total: int, shares: Dict[str, float]) -> Dict[str, int]:
    """Split `total` by `shares` exactly (largest remainders), so counts
    never depend on a seed."""
    norm = sum(shares.values())
    exact = {k: total * v / norm for k, v in shares.items()}
    out = {k: int(math.floor(x)) for k, x in exact.items()}
    rest = total - sum(out.values())
    for k in sorted(exact, key=lambda k: (out[k] - exact[k], k))[:rest]:
        out[k] += 1
    return out
