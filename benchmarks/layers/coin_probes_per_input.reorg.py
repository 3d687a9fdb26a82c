"""Native host core: coin-table probes an input of the three blocks
connected, over the timed calls: `consensus_coin_probes_total`, the tables
`view` and `block` (the disconnects' `undo` table is
`undo_probes_per_input.reorg`'s), over 18,000 x reorganisations. The count
is `coin_probes_per_input.stream`'s: an input costs one probe of the
block's table, one of the view, one in the apply, an output the same
three, and the record an apply keeps costs none: `tip-reorg.depth2`
(3 x 18,000 + 3 x 7,506) / 18,000 = 4.251."""

from benchmarks.layers._reorg import summed, timed


def read(ctx):
    d, probes = timed(ctx), summed(ctx, "connect_probes")
    if d is None or probes is None or not d.get("verdicts"):
        return None
    return probes / (d["verdicts"] * len(d["walls_s"]))
