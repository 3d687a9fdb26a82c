"""Host-device transfers a one-device dispatch makes, shared by the two
readers: `consensus_dispatch_transfers_total` over the window, both
directions (arguments put, results' host copies asked for), over
`consensus_dispatch_total` over the same window. A dispatch that travels
packed makes one piece each way, 2.0; the seven-argument launch with its
checksum program and four pulls made 11. A program without the counter has
nothing to read."""

from typing import Optional

from benchmarks.harness import counters

_NAME = "consensus_dispatch_transfers_total"
_DISPATCHES = "consensus_dispatch_total"


def per_dispatch(ctx: dict, kind: str) -> Optional[float]:
    """Pieces over dispatches in the window of a cell of `kind`; the ratio
    is the same inside the timed calls and outside them, so the window's
    own counters do."""
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != kind or not before or not after or _NAME not in after:
        return None
    dispatches = counters.rose(before, after, _DISPATCHES)
    if not dispatches:
        return None
    return counters.rose(before, after, _NAME) / dispatches
