"""`verifier.phases` `backpressure`, median per connect: the time the driver
stood before a dispatch because the in-flight queue was at its depth limit,
waiting for its oldest ticket's kernel. A program without the phase (or a
connect of no more chunks than the queue is deep) has nothing to read."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    reports = ctx["driver"].get("phases") or []
    if not any("backpressure" in rep for rep in reports):
        return None
    return median_ms(ctx, ("backpressure",))
