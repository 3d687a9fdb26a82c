"""Native host core: every phase `verifier.phases` names but `sync` (parse,
block_check, accounting, probe, interpret, host_prep, pack, dispatch,
apply, results, undo), summed between two successive results, median."""

from benchmarks.layers._stream import HOST_PHASES_NOT, median_ms


def read(ctx):
    return median_ms(ctx, but=HOST_PHASES_NOT)
