"""Names the profiler gives the verify path's device programs (jax 0.9.0,
TPU v5e, read off a trace by hand in PR 24), shared by the readers."""

from typing import Optional

from benchmarks.harness import counters
from benchmarks.harness.tracered import seconds_matching

# The programs on the `XLA Modules` line: the Pallas verify program and the
# verdict checksum chained onto every dispatch.
VERIFY_PROGRAMS = r"verify_tiles|_verify_kernel|_verdict_checksum"


def kernel_ms_per(ctx: dict, annotation: Optional[str], per_counter: Optional[str]) -> Optional[float]:
    """Device milliseconds of the verify programs per benchmark call
    (`annotation`) or per unit of a program counter over the traced slice."""
    tr = ctx.get("trace")
    if not tr:
        return None
    if annotation is not None:
        inside = tr["within"].get(annotation)
        if not inside or not inside["count"]:
            return None
        secs = seconds_matching(inside["modules"], VERIFY_PROGRAMS)
        return secs / inside["count"] * 1000.0 if secs else None
    units = counters.rose(tr["counters_before"], tr["counters_after"], per_counter)
    secs = seconds_matching(tr["modules"], VERIFY_PROGRAMS)
    return secs / units * 1000.0 if units and secs else None


def idle_share(ctx: dict, annotation: Optional[str] = None) -> Optional[float]:
    """Idle share of the device over the traced slice, or over the calls
    of one kind (`annotation`) that lie inside it."""
    tr = ctx.get("trace")
    if not tr:
        return None
    busy, span = tr["busy_s"], tr["window_s"]
    if annotation is not None:
        inside = tr["within"].get(annotation)
        if not inside:
            return None
        busy, span = inside["busy_s"], inside["span_s"]
    return (1.0 - busy / span) * 100.0 if span else None
