"""Share of the kernel's time that nobody waited for: 1 - the seconds the
host stood at the settle seam (`verifier.phases` `sync`, mean a block over
the timed passes) over the device seconds of the verify and checksum
programs a block (the traced slice), floored at 0. The wait holds a few
milliseconds that are not kernel time, so a stream with nothing overlapped
reads 0 and not a negative share."""

from benchmarks.layers._stream import between_results, kernel_ms_per_block


def read(ctx):
    waits = between_results(ctx, ("sync",))
    kernel_ms = kernel_ms_per_block(ctx)
    if waits is None or not kernel_ms:
        return None
    wait_ms = sum(waits) / len(waits) * 1000.0
    return max(0.0, 1.0 - wait_ms / kernel_ms) * 100.0
