"""Coin-table probes an input of a streamed block, over the timed passes:
`consensus_coin_probes_total` over the window, over inputs x blocks x
passes. The count is `coin_probes_per_input.connect`'s; a stream applies
every block with an undo record, which costs no probe more where the
apply's insert says whether it overwrote. `ibd-stream.cold`: 3.9285 =
(3 x 6,000 + 3 x 1,857) / 6,000."""

from benchmarks.layers._probes import per_input


def read(ctx):
    return per_input(ctx, "stream")
