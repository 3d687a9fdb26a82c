"""Lanes a connect's fixpoints sent to the device, by kind, shared by the
readers: `consensus_checks_total{kind}` over the window. A program that
does not feed the counter on the block path reads no rise and gives None."""

from typing import Dict, Optional

from benchmarks.harness import counters

_NAME = "consensus_checks_total"


def lanes_by_kind(ctx: dict) -> Optional[Dict[str, float]]:
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not before or not after:
        return None
    rose = counters.rose_by_label(before, after, _NAME, "kind")
    return rose or None


def share(ctx: dict, kind: str) -> Optional[float]:
    lanes = lanes_by_kind(ctx)
    if not lanes:
        return None
    return lanes.get(kind, 0.0) / sum(lanes.values()) * 100.0
