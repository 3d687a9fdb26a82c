"""Differences of the program's metrics registry between two snapshots."""

from __future__ import annotations

from typing import Dict, Optional, Sequence


def snapshot(names: Optional[Sequence[str]] = None) -> dict:
    """The registry's snapshot, or only the metrics in `names` (the same
    samples, without walking every metric: cheap enough for every connect)."""
    from bitcoinconsensus_tpu.obs import get_registry

    registry = get_registry()
    if names is None:
        return registry.snapshot()
    found = ((name, registry.get(name)) for name in names)
    return {name: {"samples": m._samples()} for name, m in found if m is not None}


def _samples(snap: dict, name: str) -> list:
    return snap.get(name, {"samples": []})["samples"]


def total(snap: dict, name: str) -> float:
    """A counter summed over its label sets (0 when never bumped)."""
    return sum(s["value"] for s in _samples(snap, name))


def by_label(snap: dict, name: str, label: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for s in _samples(snap, name):
        key = s["labels"].get(label, "")
        out[key] = out.get(key, 0.0) + s["value"]
    return out


def rose(before: dict, after: dict, name: str) -> float:
    return total(after, name) - total(before, name)


def rose_by_label(before: dict, after: dict, name: str, label: str) -> Dict[str, float]:
    b, a = by_label(before, name, label), by_label(after, name, label)
    return {k: v - b.get(k, 0.0) for k, v in a.items() if v - b.get(k, 0.0)}


def histogram_mean(before: dict, after: dict, name: str) -> Optional[float]:
    """Mean of the observations a histogram took between two snapshots:
    exact, where a quantile could only be placed between bucket edges.
    None when it took none."""
    def sums(snap):
        ss = _samples(snap, name)
        return sum(s["sum"] for s in ss), sum(s["count"] for s in ss)

    (s0, c0), (s1, c1) = sums(before), sums(after)
    if c1 - c0 <= 0:
        return None
    return (s1 - s0) / (c1 - c0)
