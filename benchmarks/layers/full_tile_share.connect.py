"""Grid steps of the verify kernel that ran the dense tile (8 sublane rows
of 128 lanes: a limb of a field element is one full register), as a share
of all its grid steps in the timed connects:
`consensus_dispatch_tiles_total{rows="8"}` over every `rows`. 100 where
every dispatch is a multiple of 1,024 lanes (the 8,192-lane chunk, the
mesh's 2,048-row shards), 0 where a connect sends the 512-lane shape, whose
one tile is half filled (`rows="4"`) and costs what a full one does.

The counter is read over the window. Where the window dispatched outside
its timed connects too (the warm cell's precharge), the connects' part is
what their own dispatches and padded lanes (`deltas`) leave possible: a
step covers `rows` x 128 lanes and a dispatch runs at least one, and the
reader answers only where one split of the window's steps fits both
sides. A program without the counter has nothing to read."""

from benchmarks.harness import counters

_NAME = "consensus_dispatch_tiles_total"
_DISPATCHES = "consensus_dispatch_total"
_PADDED = "consensus_dispatch_padded_lanes_total"
_HALF, _FULL = 4 * 128, 8 * 128  # lanes a grid step covers, by its `rows`


def _timed_steps(full, half, timed, untimed):
    """(full, half) grid steps of the timed connects, given the window's
    and each side's (dispatches, padded lanes); None unless exactly one
    split fits."""
    fits = []
    for h in range(int(half) + 1):
        f, odd = divmod(timed[1] - h * _HALF, _FULL)
        if f < 0 or odd or f > full:
            continue
        if (f + h >= timed[0] and (full - f) + (half - h) >= untimed[0]
                and (full - f) * _FULL + (half - h) * _HALF == untimed[1]):
            fits.append((f, h))
    return fits[0] if len(fits) == 1 else None


def read(ctx):
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not d.get("deltas") or not before or not after:
        return None
    if _NAME not in after:
        return None
    steps = counters.rose_by_label(before, after, _NAME, "rows")
    full, half = steps.get("8", 0.0), steps.get("4", 0.0)
    timed = tuple(sum(x[n] for x in d["deltas"]) for n in (_DISPATCHES, _PADDED))
    untimed = (counters.rose(before, after, _DISPATCHES) - timed[0],
               counters.rose(before, after, _PADDED) - timed[1])
    if untimed[0]:
        split = _timed_steps(full, half, timed, untimed)
        if split is None:
            return None
        full, half = split
    if not full + half:
        return None
    return full / (full + half) * 100.0
