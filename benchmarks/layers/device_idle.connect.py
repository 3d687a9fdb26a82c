"""Share of the timed connects' own time in which no operation ran on the
device: 1 - union of device-op intervals inside the `bench.connect`
annotations of the traced slice, over their length. (The result line's
`busy_s` and `window_s` are of the whole slice, untimed resets included.)"""

from benchmarks.layers._trace import idle_share


def read(ctx):
    return idle_share(ctx, "bench.connect") if ctx["driver"].get("kind") == "connect" else None
