"""Batch / block driver: the worker's host work a coalesced batch: seconds
in the spans `batch.stream_begin` + `batch.stream_finish` (a batch's two
halves in `verify_batch_stream`) less those in `verifier.sync` (the wait at
the settle seam inside the second half), over the window."""

from benchmarks.layers._spans import ms_per_batch


def read(ctx):
    return ms_per_batch(ctx, ("batch.stream_begin", "batch.stream_finish"), ("verifier.sync",))
