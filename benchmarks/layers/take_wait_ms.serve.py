"""Admission and coalescing: the worker's waits inside a burst, with begun
batches in its hands: seconds in the span `serving.take` (the non-blocking
take after each batch it hands the driver: at once on an empty queue, else
the rest of the oldest queued request's flush interval) over the window,
a coalesced batch. The blocking wait between bursts is `serving.idle` and
is not in here."""

from benchmarks.layers._spans import ms_per_batch


def read(ctx):
    return ms_per_batch(ctx, ("serving.take",))
