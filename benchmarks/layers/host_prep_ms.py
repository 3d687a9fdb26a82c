"""Native host core: `verifier.phases` `host_prep` + `pack`, median per connect."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    return median_ms(ctx, ("host_prep", "pack"))
