"""Kernels: device time of the verify programs inside a timed
reorganisation (`bench.reorg`): two 512-lane tiles and one 8,192-lane
dispatch, from the profiler trace."""

from benchmarks.layers._reorg import timed
from benchmarks.layers._trace import kernel_ms_per


def read(ctx):
    return None if timed(ctx) is None else kernel_ms_per(ctx, "bench.reorg", None)
