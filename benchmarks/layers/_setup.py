"""What the set-up readers share: `consensus_compile_seconds_total{stage}`,
the seconds JAX's own events (`jax.monitoring`) gave to tracing, lowering
and compiling, read in the driver's `counters_before`: the registry as the
window opened, so the whole set-up and nothing of the window."""

from typing import Optional, Sequence

from benchmarks.harness import counters

COMPILE_SECONDS = "consensus_compile_seconds_total"


def setup_seconds(ctx: dict, stages: Sequence[str]) -> Optional[float]:
    snap = ctx["driver"].get("counters_before") or {}
    if COMPILE_SECONDS not in snap:
        return None
    by_stage = counters.by_label(snap, COMPILE_SECONDS, "stage")
    return sum(by_stage.get(s, 0.0) for s in stages)
