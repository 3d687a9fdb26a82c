"""Share of the kernel's time that nobody waited for, in a connect: 1 - the
host's wait for the device (`verifier.phases` `sync`, at the settle seam,
plus `backpressure`, at the queue's depth limit; median per connect) over
`kernel_ms.connect`, floored at 0. A connect of one chunk has nothing to
lie under its kernel and reads 0; one of ten chunks can prepare chunk k+1
under the kernel of chunk k. (A program without the `backpressure` phase
counts that wait inside `dispatch`, where this reader does not see it, and
so reads too high.)"""

from benchmarks.layers._phases import median_ms
from benchmarks.layers._trace import kernel_ms_per


def read(ctx):
    wait_ms = median_ms(ctx, ("sync", "backpressure"))
    kernel_ms = kernel_ms_per(ctx, "bench.connect", None)
    if wait_ms is None or not kernel_ms:
        return None
    return max(0.0, 1.0 - wait_ms / kernel_ms) * 100.0
