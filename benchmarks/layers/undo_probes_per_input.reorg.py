"""Native host core: the view's probes by the timed disconnects, an input
they restored: `consensus_coin_probes_total{table="undo"}` over the timed
calls / (disconnected inputs x reorganisations). A disconnect finds and
erases each output of the block in one probe and inserts each spent coin in
one, so the figure is (inputs + outputs) / inputs of the two blocks
disconnected: `tip-reorg.depth2` (12,000 + 5,104) / 12,000 = 1.42533."""

from benchmarks.layers._reorg import summed, timed


def read(ctx):
    d, probes = timed(ctx), summed(ctx, "undo_probes")
    if d is None or probes is None or not d.get("disconnected_inputs"):
        return None
    return probes / (d["disconnected_inputs"] * len(d["walls_s"]))
