"""Milliseconds a connect of the serial head of lane prep: stage `lanes/order`
of `consensus_native_stage_seconds_total`, the `lanes_order` loop of
`nat_session_uniq_lanes` over a chunk's entries before its fan-out, every
chunk of the connect. Inside the `host_prep` phase. A window mean
(`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "connect", "lanes", "order")
