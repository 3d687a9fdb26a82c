"""Device: share of the timed reorganisations' own time in which no
operation ran on the device: 1 - union of device-op intervals inside the
`bench.reorg` annotations of the traced slice, over their length. The two
disconnects reach no device at all."""

from benchmarks.layers._reorg import timed
from benchmarks.layers._trace import idle_share


def read(ctx):
    return None if timed(ctx) is None else idle_share(ctx, "bench.reorg")
