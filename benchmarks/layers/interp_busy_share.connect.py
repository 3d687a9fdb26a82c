"""Per cent of the thread seconds the interpreter's fan-outs held that its
workers were busy: `sum` over `held` of `consensus_fan_out_seconds_total`,
call `interpret`, over the window. The rest is threads not yet started,
out of inputs early or being joined."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.busy_share(ctx, "connect", ("interpret",))
