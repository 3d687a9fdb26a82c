"""Mean of `consensus_serving_batch_seconds` (flush to verdict delivery of
a coalesced batch) over the window; exact, see queue_wait_ms."""

from benchmarks.harness.counters import histogram_mean


def read(ctx):
    d = ctx["driver"]
    m = histogram_mean(d["counters_before"], d["counters_after"],
                       "consensus_serving_batch_seconds")
    return None if m is None else m * 1000.0
