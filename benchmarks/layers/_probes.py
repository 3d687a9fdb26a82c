"""Hash-table probes the native accounting and apply made, shared by the two
readers: `consensus_coin_probes_total` over the window, every `table` (the
view, and pass 1's table of the block's own coins), inputs' and outputs'
probes alike. A program without the counter has nothing to read."""

from typing import Optional

from benchmarks.harness import counters

_NAME = "consensus_coin_probes_total"


def per_input(ctx: dict, kind: str) -> Optional[float]:
    """Probes over inputs x blocks connected in the window's timed calls: a
    connect is one block, a pass of the stream its chain's `n_blocks`."""
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != kind or not before or not after or _NAME not in after:
        return None
    calls = d["walls_s"] if kind == "connect" else d["pass_walls_s"]
    verified = d["n_inputs"] * d.get("n_blocks", 1) * len(calls)
    if not verified:
        return None
    return counters.rose(before, after, _NAME) / verified
