"""Host-device transfers a dispatch of the streamed blocks makes, both ways:
`consensus_dispatch_transfers_total` over `consensus_dispatch_total`, over
the window. The count is `transfers_per_dispatch.connect`'s; in a stream the
settle's pieces are what `settle_wait_ms.stream` pays for once the kernel
has long finished."""

from benchmarks.layers._transfers import per_dispatch


def read(ctx):
    return per_dispatch(ctx, "stream")
