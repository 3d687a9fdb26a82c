"""What the readers of the native stage clock share: the rise over the
window of `consensus_native_stage_seconds_total{call,stage}` (the serial
stages that tile a native call) and of `consensus_fan_out_seconds_total
{call,stat}` (what a thread fan-out says of itself), over the window's timed
calls, in milliseconds. A window MEAN: the drivers difference only their own
counters around each call, so one slow call of a window lifts these where a
median of the phases ignores it; hold a stage against the mean of its phase's
`secs` over the same calls (`ctx["driver"]["phases"]`), not its median.
A program without the family (the parent commit) has nothing to read."""

from typing import Iterable, Optional, Tuple

STAGES = "consensus_native_stage_seconds_total"
FAN_OUT = "consensus_fan_out_seconds_total"
SESSION_CALLS = ("interpret", "lanes", "digests")


def _calls(ctx: dict, kind: str) -> Optional[int]:
    """The window's timed calls of a cell of `kind`: its connects, or the
    blocks of every pass of a stream. None in any other cell."""
    d = ctx["driver"]
    if d.get("kind") != kind:
        return None
    if kind == "connect":
        return len(d["walls_s"])
    return d["n_blocks"] * len(d["pass_walls_s"])


def _rose(ctx: dict, name: str, pairs: Iterable[Tuple[str, str]], second: str) -> Optional[float]:
    """Seconds `name` rose by over the window, summed over the (`call`,
    `second` label) pairs; None without both snapshots or the family."""
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if not before or not after or name not in after:
        return None
    pairs = set(pairs)

    def total(snap):
        return sum(s["value"] for s in snap.get(name, {"samples": []})["samples"]
                   if (s["labels"].get("call"), s["labels"].get(second)) in pairs)

    return total(after) - total(before)


def _per_call_ms(ctx: dict, kind: str, seconds: Optional[float]) -> Optional[float]:
    calls = _calls(ctx, kind)
    if seconds is None or not calls:
        return None
    return seconds / calls * 1000.0


def stage_ms(ctx: dict, kind: str, call: str, stage: str) -> Optional[float]:
    """Milliseconds of one stage a timed call."""
    return _per_call_ms(ctx, kind, _rose(ctx, STAGES, [(call, stage)], "stage"))


def fan_ms(ctx: dict, kind: str, calls: Iterable[str], stat: str) -> Optional[float]:
    """Milliseconds of one fan-out stat a timed call, summed over `calls`."""
    return _per_call_ms(ctx, kind, _rose(ctx, FAN_OUT, [(c, stat) for c in calls], "stat"))


def busy_share(ctx: dict, kind: str, calls: Iterable[str]) -> Optional[float]:
    """Per cent of the thread seconds `calls`' fan-outs held (`held`: width x
    wall) that their workers were busy (`sum`); None where they held none."""
    calls = tuple(calls)
    if not _calls(ctx, kind):
        return None
    held = _rose(ctx, FAN_OUT, [(c, "held") for c in calls], "stat")
    busy = _rose(ctx, FAN_OUT, [(c, "sum") for c in calls], "stat")
    if held is None or busy is None or not held > 0:
        return None
    return 100.0 * busy / held
