"""Native host core: every phase `verifier.phases` names but `sync` (the
disconnects' parse, undo_check, undo and block_free; the connects' parse,
block_check, accounting, probe, interpret, host_prep, pack, dispatch,
apply, results and the rest), summed over a reorganisation, median."""

from benchmarks.layers._reorg import median_ms, timed


def read(ctx):
    d = timed(ctx)
    if d is None or not all(d["phases"]):
        return None
    return median_ms([sum(p["secs"] for n, p in rep.items() if n != "sync")
                      for rep in d["phases"]])
