"""Coin-table probes an input of the block, over the timed connects:
`consensus_coin_probes_total` over the window, over inputs x connects. By
the count the counter keeps (a find, an insert or an erase by outpoint), an
input costs one probe of the block's own table, one of the view unless the
block made the coin, and one in the apply; an output one of the view
(BIP30), one of the block's table and one in the apply: at most
(3 x inputs + 3 x outputs) / inputs, 4.201 for `tip-block.cold`'s 6,000
inputs and 2,402 outputs. Keying pass 1 by three tables (spent set, overlay,
view) made it (5 x inputs + 3 x outputs) / inputs, 6.201 there."""

from benchmarks.layers._probes import per_input


def read(ctx):
    return per_input(ctx, "connect")
