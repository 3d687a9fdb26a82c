"""Native host core: `verifier.phases` `interpret`, median per connect."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    return median_ms(ctx, ("interpret",))
