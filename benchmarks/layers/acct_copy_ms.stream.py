"""Milliseconds a streamed block of the accounting's copy-out (the body of
`nat_block_acct_data`: five arrays copied into the bridge's buffers): stage
`accounting/copy` of `consensus_native_stage_seconds_total` over blocks x
timed passes. Inside the `accounting` phase. A window mean (`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "stream", "accounting", "copy")
