"""Dispatch: `verifier.phases` `dispatch` (the launch calls, enqueue only
once a shape is warm), median per connect."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    return median_ms(ctx, ("dispatch",))
