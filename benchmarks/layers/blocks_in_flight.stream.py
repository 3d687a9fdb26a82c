"""Batch / block driver: mean of the program's
`consensus_stream_blocks_in_flight` observations over the window: blocks
begun and not yet finished each time one was begun, that one included."""

from benchmarks.harness import counters


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "stream" or d.get("counters_before") is None:
        return None
    return counters.histogram_mean(d["counters_before"], d["counters_after"],
                                   "consensus_stream_blocks_in_flight")
