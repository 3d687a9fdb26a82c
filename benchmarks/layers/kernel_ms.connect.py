"""Device time of the verify and checksum programs inside a timed connect,
per connect, from the profiler trace."""

from benchmarks.layers._trace import kernel_ms_per


def read(ctx):
    return kernel_ms_per(ctx, "bench.connect", None)
