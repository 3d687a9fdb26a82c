"""Share of the timed passes' own time in which no operation ran on the
device: 1 - union of device-op intervals inside the `bench.stream`
annotations of the traced slice, over their length."""

from benchmarks.layers._trace import idle_share


def read(ctx):
    return idle_share(ctx, "bench.stream") if ctx["driver"].get("kind") == "stream" else None
