"""Milliseconds a connect around lane prep's fan-out: stage `lanes/shards` of
`consensus_native_stage_seconds_total`, `prep_lanes_impl` inside
`nat_session_uniq_lanes` (threads made, the shards' three passes, joined),
every chunk of the connect. Inside the `host_prep` phase. A window mean
(`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "connect", "lanes", "shards")
