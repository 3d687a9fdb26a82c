"""Share of the traced slice in which no operation ran on the device."""

from benchmarks.layers._trace import idle_share


def read(ctx):
    return idle_share(ctx) if ctx["driver"].get("kind") == "serve" else None
