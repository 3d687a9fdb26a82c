"""Milliseconds a connect of the sig cache's key digests: stage
`digests/shards` of `consensus_native_stage_seconds_total`, the whole of
`nat_session_uniq_digests` (its fan-out over a round's new entries), every
round of the connect. Inside the `host_prep` phase. A window mean
(`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "connect", "digests", "shards")
