"""Milliseconds a streamed block of pass 2 of the native accounting
(`block_acct_fill`: the per-input records, the spent-output digests, the hash
precomputes, the script cache's keys): stage `accounting/fill` of
`consensus_native_stage_seconds_total` over blocks x timed passes. Inside the
`accounting` phase. A window mean (`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "stream", "accounting", "fill")
