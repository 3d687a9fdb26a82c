"""Native host core: what ends with a connect, `verifier.phases`
`block_free` (the parsed block, and a speculative apply's undo record,
freed after their last reader) + `release` (the native session freed after
the fixpoint), median per connect. None on a program with no `block_free`."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    reports = ctx["driver"].get("phases") or []
    if not any("block_free" in rep for rep in reports):
        return None
    return median_ms(ctx, ("block_free", "release"))
