"""Schnorr lanes as a share of the lanes the timed connects sent to the
device: `consensus_checks_total{kind="schnorr"}` over all kinds, over the
window. 73.1 in `taproot-block.cold` (10,260 of 14,040), 15.4 in
`tip-block.cold` (1,200 of 7,800). A program that does not feed the counter
in a connect has nothing to read."""

from benchmarks.layers._lanes import share


def read(ctx):
    return share(ctx, "schnorr")
