"""Batch and block driver: what no phase names in a reorganisation: its
wall (first disconnect call to `B3`'s result) minus the `outer_secs` of
every phase `verifier.phases` timed in it (a phase counts only while it is
the outermost open one on the caller's thread, so nothing is counted
twice), median: the seams between phases, the generator's turns, the
driver's own loop."""

from benchmarks.layers._reorg import median_ms, timed


def read(ctx):
    d = timed(ctx)
    if d is None or len(d["phases"]) != len(d["walls_s"]) or not all(
            "outer_secs" in p for rep in d["phases"] for p in rep.values()):
        return None
    return median_ms([wall - sum(p["outer_secs"] for p in rep.values())
                      for wall, rep in zip(d["walls_s"], d["phases"], strict=True)])
