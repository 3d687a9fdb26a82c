"""Batch / block driver: connect wall minus what `verifier.phases` gives
the native core (interpret), lane prep (host_prep, pack), the launch
(dispatch) and the settle wait (sync); median per connect."""

from benchmarks.harness.stats import median
from benchmarks.layers._phases import per_connect


def read(ctx):
    inner = per_connect(ctx, ("interpret", "host_prep", "pack", "dispatch", "sync"))
    if inner is None:
        return None
    walls = ctx["driver"]["walls_s"]
    return median([w - p for w, p in zip(walls, inner, strict=True)]) * 1000.0
