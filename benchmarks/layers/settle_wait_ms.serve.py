"""Dispatch and settle: the worker's wait at the settle seam, seconds in
the span `verifier.sync` over the window, a coalesced batch (host wait, not
kernel time)."""

from benchmarks.layers._spans import ms_per_batch


def read(ctx):
    return ms_per_batch(ctx, ("verifier.sync",))
