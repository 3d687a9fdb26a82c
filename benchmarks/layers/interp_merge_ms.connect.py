"""Milliseconds a connect of the interpreter's serial tail: stage
`interpret/merge` of `consensus_native_stage_seconds_total`, from the join
to the return of `nat_verify_inputs_idx` (the scratches' counters summed,
the merge in index order, the scratches freed), every round of the connect.
Inside the `interpret` phase. A window mean (`_stages.py`)."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.stage_ms(ctx, "connect", "interpret", "merge")
