"""Host-device transfers a dispatch of the timed connects makes, both ways:
`consensus_dispatch_transfers_total` over `consensus_dispatch_total`, over
the window. 2.0 where every dispatch is one packed buffer put and one
result's host copy asked for; a launch costs the host by the piece, not by
the byte (`launch_ms.connect`, `settle_wait_ms`)."""

from benchmarks.layers._transfers import per_dispatch


def read(ctx):
    return per_dispatch(ctx, "connect")
