"""Requests shed over requests shed or admitted, in the window."""

from benchmarks.harness.counters import rose


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "serve":
        return None
    shed = rose(d["counters_before"], d["counters_after"], "consensus_serving_shed_total")
    admitted = rose(d["counters_before"], d["counters_after"], "consensus_serving_admitted_total")
    if not shed + admitted:
        return None
    return shed / (shed + admitted) * 100.0
