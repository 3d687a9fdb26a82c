"""Device time of the verify and checksum programs per coalesced batch
(`consensus_serving_batches_total` over the traced slice)."""

from benchmarks.layers._trace import kernel_ms_per


def read(ctx):
    return kernel_ms_per(ctx, None, "consensus_serving_batches_total")
