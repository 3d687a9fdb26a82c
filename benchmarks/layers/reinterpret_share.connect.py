"""Inputs a fixpoint round after the first interpreted again, as a share
of the inputs the timed connects verified:
`consensus_fixpoint_reinterpreted_inputs_total` over the window, over
inputs x connects. 100 where every input's first guess was wrong. A
program without the counter has nothing to read."""

from benchmarks.harness import counters

_NAME = "consensus_fixpoint_reinterpreted_inputs_total"


def read(ctx):
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not before or not after or _NAME not in after:
        return None
    verified = d["n_inputs"] * len(d["walls_s"])
    if not verified:
        return None
    return counters.rose(before, after, _NAME) / verified * 100.0
