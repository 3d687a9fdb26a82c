"""Milliseconds a connect between a fan-out's entry and its latest worker's
first instruction, summed over every fan-out of the connect: `start_lag` of
`consensus_fan_out_seconds_total`, calls `interpret`, `lanes` and
`digests`. The `pthread_create` loop and the scheduler: what a worker pool
could take off. A window mean (`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.fan_ms(ctx, "connect", _stages.SESSION_CALLS, "start_lag")
