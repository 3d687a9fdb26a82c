"""Dispatch and settle: the share of the kernel's time that nobody waited
for: 1 - the seconds the host stood at the settle seam (`verifier.phases`
`sync`, mean a reorganisation over the window) over the device seconds of
the verify programs a reorganisation (`kernel_ms.reorg`, the traced
slice), floored at 0 as `overlap_share.stream` is: the wait holds a pull
and its checks, which are not kernel time."""

from benchmarks.layers._reorg import timed
from benchmarks.layers._trace import kernel_ms_per


def read(ctx):
    d = timed(ctx)
    if d is None or not all("sync" in rep for rep in d["phases"]):
        return None
    kernel_ms = kernel_ms_per(ctx, "bench.reorg", None)
    if not kernel_ms:
        return None
    wait_ms = sum(rep["sync"]["secs"] for rep in d["phases"]) / len(d["phases"]) * 1000.0
    return max(0.0, 1.0 - wait_ms / kernel_ms) * 100.0
