"""Dispatch and settle: `verifier.phases` `sync`, the host's wait at the
settle seam, summed over a reorganisation's three dispatches, median."""

from benchmarks.layers._reorg import median_ms, timed


def read(ctx):
    d = timed(ctx)
    if d is None or not all("sync" in rep for rep in d["phases"]):
        return None
    return median_ms([rep["sync"]["secs"] for rep in d["phases"]])
