"""Mean of `consensus_serving_batch_fill`: coalesced batch size over the
flush target."""

from benchmarks.harness.counters import histogram_mean


def read(ctx):
    d = ctx["driver"]
    m = histogram_mean(d["counters_before"], d["counters_after"],
                       "consensus_serving_batch_fill")
    return None if m is None else m * 100.0
