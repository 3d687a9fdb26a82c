"""Per-connect host seconds from `verifier.phases`, shared by the readers."""

from typing import List, Optional, Sequence

from benchmarks.harness.stats import median


def per_connect(ctx: dict, names: Sequence[str]) -> Optional[List[float]]:
    """For each timed connect, the seconds `verifier.phases` gave to the
    phases in `names` together; None outside a connect cell."""
    d = ctx["driver"]
    if d.get("kind") != "connect" or not d["phases"]:
        return None
    return [sum(rep.get(n, {}).get("secs", 0.0) for n in names) for rep in d["phases"]]


def median_ms(ctx: dict, names: Sequence[str]) -> Optional[float]:
    secs = per_connect(ctx, names)
    return None if secs is None else median(secs) * 1000.0
