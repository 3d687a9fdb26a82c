"""Batch and block driver: the median of every timed `disconnect_block`
call, on the host's clock: a raw block parsed, its record held against it,
its outputs taken out of the view and the coins it spent put back."""

from benchmarks.layers._reorg import median_ms, timed


def read(ctx):
    d = timed(ctx)
    return None if d is None else median_ms(d["disconnect_s"])
