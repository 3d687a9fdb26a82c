"""Per cent of the thread seconds lane prep's and the digests' fan-outs held
that their workers were busy: `sum` over `held` of
`consensus_fan_out_seconds_total`, calls `lanes` and `digests`, over the
window. The rest is threads not yet started, ended early or being joined."""

from benchmarks.layers import _stages


def read(ctx):
    return _stages.busy_share(ctx, "connect", ("lanes", "digests"))
