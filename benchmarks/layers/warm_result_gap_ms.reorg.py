"""Batch and block driver: the cadence of the results of the two blocks
whose transactions the caches hold to 95 %: the gaps first connect call ->
`B1`'s result and `B1` -> `B2`, median of both over every reorganisation.
A gap is the stream's, not a block's: at depth 2 the first holds the
begins of `B1` and `B2` and `B1`'s finish, the second `B3`'s whole begin
under `B2`'s lanes and `B2`'s finish."""

from benchmarks.layers._reorg import gaps_ms


def read(ctx):
    return gaps_ms(ctx, (0, 1))
