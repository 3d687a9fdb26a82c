"""Batch / block driver: the median gap between successive `ConnectResult`s
inside a pass, on the host's clock: what one more block costs a stream."""

from benchmarks.harness.stats import median


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "stream" or not d["block_gaps_s"]:
        return None
    return median(d["block_gaps_s"]) * 1000.0
