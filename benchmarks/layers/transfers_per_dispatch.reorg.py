"""Dispatch and settle: host-device transfers a dispatch of a timed
reorganisation makes, both ways: `consensus_dispatch_transfers_total` over
`consensus_dispatch_total`, over the timed calls. A dispatch that travels
packed makes one piece each way: 2.0, for the two 512-lane tiles and the
8,192-lane dispatch alike."""

from benchmarks.layers._reorg import summed

_NAME = "consensus_dispatch_transfers_total"


def read(ctx):
    pieces, dispatches = summed(ctx, _NAME), summed(ctx, "consensus_dispatch_total")
    return pieces / dispatches if pieces is not None and dispatches else None
