"""Device time of the verify and checksum programs inside a timed pass of
the stream, a block, from the profiler trace."""

from benchmarks.layers._stream import kernel_ms_per_block


def read(ctx):
    return kernel_ms_per_block(ctx)
