"""Checks that a run really went through the chip.

The dispatch path is built to survive a broken device: a launch that
raises is retried, the ladder demotes, and the host oracle answers, so
verdicts stay right and the process exits 0 (`resilience/inflight.py`,
`resilience/degrade.py`). That is the product's guarantee and it stays.
Anything that *proves* the chip path — `chip_smoke.py`,
`scripts/tpu_differential.py` — must therefore refuse to start below a TPU and must fail if any of that
machinery engaged. These helpers are that refusal, shared.
"""

from __future__ import annotations

import sys
from typing import Dict, Optional

__all__ = [
    "ChipPathError",
    "ZERO_COUNTERS",
    "assert_clean",
    "counter_total",
    "device_info",
    "dispatches",
    "fallback_counters",
    "require_tpu",
    "samples",
]

# Counters that stay at zero while every verdict comes off the device's
# top rung. Any of them rising means the run fell back somewhere.
ZERO_COUNTERS = (
    "consensus_resilience_demotions_total",
    "consensus_resilience_retries_total",
    "consensus_resilience_contained_total",
    "consensus_resilience_host_exact_lanes_total",
    "consensus_resilience_guard_anomalies_total",
    "consensus_inflight_failures_total",
    "consensus_inflight_deadline_expired_total",
    "consensus_host_fixup_total",
    "consensus_backend_config_errors_total",
)


class ChipPathError(RuntimeError):
    """The run did not stay on the device path it claims to exercise."""


def device_info() -> Dict[str, object]:
    """The device as JAX reports it; every result line carries this."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu() -> Dict[str, object]:
    """`device_info()`, or exit 2 naming what JAX found instead of a TPU.
    Nothing is printed to stdout: no chip, no result."""
    dev = device_info()
    if dev["platform"] != "tpu":
        print(
            f"refusing to run: needs a TPU, but jax.devices()[0].platform is "
            f"{dev['platform']!r} ({dev['kind']}, {dev['count']} device(s)). "
            "A run below the chip proves nothing about it.",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return dev


def samples(name: str, snapshot: Optional[dict] = None) -> list:
    """One metric's samples ([] when not registered or never bumped)."""
    if snapshot is None:
        from bitcoinconsensus_tpu.obs import get_registry

        snapshot = get_registry().snapshot()
    return snapshot.get(name, {"samples": []})["samples"]


def counter_total(name: str, snapshot: Optional[dict] = None) -> float:
    """A registry counter summed over its label sets."""
    return sum(s["value"] for s in samples(name, snapshot))


def dispatches() -> Dict[str, int]:
    """Device dispatches so far in this process, by the backend that ran."""
    return {
        s["labels"]["backend"]: int(s["value"])
        for s in samples("consensus_dispatch_total")
    }


def fallback_counters() -> Dict[str, float]:
    """Current totals of `ZERO_COUNTERS`, from one registry snapshot."""
    from bitcoinconsensus_tpu.obs import get_registry

    snapshot = get_registry().snapshot()
    return {name: counter_total(name, snapshot) for name in ZERO_COUNTERS}


def assert_clean(
    verifier, where: str, since: Optional[Dict[str, float]] = None
) -> None:
    """Raise `ChipPathError` unless `verifier` is on its top rung and no
    fallback counter moved since the `fallback_counters()` snapshot `since`
    (default: since zero — a fresh process). The message carries the
    queue's last recorded failure: the compiler's or runtime's own text."""
    problems = []
    ladder = verifier._resilience.ladder
    if ladder.current != ladder.levels[0]:
        problems.append(
            f"ladder on rung {ladder.current!r}, top is {ladder.levels[0]!r}"
        )
    for name, total in fallback_counters().items():
        rose = total - (since or {}).get(name, 0)
        if rose:
            problems.append(f"{name} +{rose:g}")
    if not problems:
        return
    failure = verifier._inflight.last_failure
    if failure is not None:
        problems.append(
            "last failure: {stage} at level {level!r}, shape {shape}, "
            "{lanes} lanes, attempt {attempt}: {exc}: {error}".format(**failure)
        )
    raise ChipPathError(f"{where}: left the device path: " + "; ".join(problems))
