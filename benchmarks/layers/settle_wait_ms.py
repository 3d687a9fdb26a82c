"""Settle: `verifier.phases` `sync`, the host's wait at the settle seam
(not kernel time), median per connect."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    return median_ms(ctx, ("sync",))
