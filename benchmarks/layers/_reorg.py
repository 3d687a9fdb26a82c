"""What the reorganisation cell's readers share: the driver's record of
every sound timed reorganisation (two `disconnect_block` calls, then three
blocks through one stream). None outside such a cell, or where the window
timed none."""

from typing import List, Optional

from benchmarks.harness.stats import median


def timed(ctx: dict) -> Optional[dict]:
    d = ctx["driver"]
    return d if d.get("kind") == "reorg" and d.get("walls_s") else None


def median_ms(seconds: List[float]) -> Optional[float]:
    return median(seconds) * 1000.0 if seconds else None


def gaps_ms(ctx: dict, which) -> Optional[float]:
    """Median over every reorganisation of the gaps at the positions
    `which`: 0 is the first connect call to `B1`'s result, 1 `B1` to `B2`,
    2 `B2` to `B3`."""
    d = timed(ctx)
    if d is None:
        return None
    return median_ms([gaps[i] for gaps in d["gaps_s"] for i in which if i < len(gaps)])


def summed(ctx: dict, name: str) -> Optional[float]:
    """A per-reorganisation counter difference, summed over the window's
    timed calls; None where the program has no such counter."""
    d = timed(ctx)
    if d is None or any(name not in x for x in d["deltas"]):
        return None
    return sum(x[name] for x in d["deltas"])
