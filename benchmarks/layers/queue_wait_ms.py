"""Mean of `consensus_serving_queue_wait_seconds` over the window. The
histogram has fixed buckets, so its mean is exact where a median could only
be placed between two bucket edges."""

from benchmarks.harness.counters import histogram_mean


def read(ctx):
    d = ctx["driver"]
    m = histogram_mean(d["counters_before"], d["counters_after"],
                       "consensus_serving_queue_wait_seconds")
    return None if m is None else m * 1000.0
