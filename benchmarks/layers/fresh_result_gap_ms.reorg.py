"""Batch and block driver: the gap `B2`'s result -> `B3`'s, the block the
caches know a tenth of, median over every reorganisation. `B3` is begun
while `B2`'s lanes are out, so the gap holds `B3`'s finish alone: its
settle, its verdicts, its commit."""

from benchmarks.layers._reorg import gaps_ms


def read(ctx):
    return gaps_ms(ctx, (2,))
