"""Taproot tweak checks (a script-path spend's commitment: the output key
against its internal key and the tree's root) as a share of the lanes the
timed connects sent to the device: `consensus_checks_total{kind="tweak"}`
over all kinds, over the window. 15.4 in `taproot-block.cold` (2,160 of
14,040). A program that does not feed the counter in a connect has nothing
to read."""

from benchmarks.layers._lanes import share


def read(ctx):
    return share(ctx, "tweak")
