"""Settle: `verifier.phases` `sync`, the host's wait at the settle seam,
between two successive results of a stream, median. What the overlap is
there to shrink: at depth 1 it is the kernel's whole time and more."""

from benchmarks.layers._stream import median_ms


def read(ctx):
    return median_ms(ctx, ("sync",))
