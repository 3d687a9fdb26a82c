"""Milliseconds a streamed block of pass 1 of the native accounting
(`block_acct_decide`: the BIP30 scan, existence, maturity, values, fees, the
sigop budget): stage `accounting/decide` of
`consensus_native_stage_seconds_total` over blocks x timed passes. Inside the
`accounting` phase. A window mean (`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "stream", "accounting", "decide")
