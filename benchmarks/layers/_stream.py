"""What the stream cell's readers share: the host seconds `verifier.phases`
timed between two successive `ConnectResult`s of a pass. In the steady part
of a pass such a stretch holds one block's begin and another's finish."""

from typing import List, Optional, Sequence

from benchmarks.harness.stats import median

HOST_PHASES_NOT = ("sync",)  # every phase but the wait at the settle seam is host work


def between_results(ctx: dict, names: Optional[Sequence[str]] = None,
                    but: Sequence[str] = ()) -> Optional[List[float]]:
    """For each result of each timed pass, the seconds of the phases in
    `names` (all, when None) except those in `but`; None outside a stream
    cell."""
    d = ctx["driver"]
    if d.get("kind") != "stream" or not d["phases"]:
        return None
    return [
        sum(secs for n, secs in rep.items() if (names is None or n in names) and n not in but)
        for rep in d["phases"]
    ]


def median_ms(ctx: dict, names: Optional[Sequence[str]] = None,
              but: Sequence[str] = ()) -> Optional[float]:
    secs = between_results(ctx, names, but)
    return None if secs is None else median(secs) * 1000.0


def kernel_ms_per_block(ctx: dict) -> Optional[float]:
    """Device milliseconds of the verify and checksum programs inside the
    timed passes of the traced slice, a block."""
    from benchmarks.layers._trace import kernel_ms_per

    if ctx["driver"].get("kind") != "stream":
        return None
    per_pass = kernel_ms_per(ctx, "bench.stream", None)
    return None if per_pass is None else per_pass / ctx["driver"]["n_blocks"]
