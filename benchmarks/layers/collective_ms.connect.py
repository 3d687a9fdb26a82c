"""Device time of the all-reduce operations inside a timed connect, per
connect, mean over the chips: the `XLA Ops` events whose name holds
`all-reduce` (the psum that ANDs a dispatch's shards into one verdict, the
mesh's stand-in for `CCheckQueueControl::Wait`), inside the `bench.connect`
annotations of the traced slice. A trace of one chip's programs holds no
such operation and has nothing to read."""

from benchmarks.harness.tracered import seconds_matching

COLLECTIVES = r"all-reduce"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    inside = tr["within"].get("bench.connect")
    if not inside or not inside["count"]:
        return None
    secs = seconds_matching(inside["ops"], COLLECTIVES)
    return secs / inside["count"] * 1000.0 if secs else None
