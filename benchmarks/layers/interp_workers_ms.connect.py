"""Milliseconds a connect around the interpreter's fan-out: stage
`interpret/workers` of `consensus_native_stage_seconds_total`, every round
of the connect (threads made, the workers drawing inputs from the shared
cursor, joined). Inside the `interpret` phase. A window mean
(`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "connect", "interpret", "workers")
