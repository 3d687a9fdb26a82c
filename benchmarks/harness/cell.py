"""What one run of one cell is held to, shared by the drivers."""

from __future__ import annotations

from contextlib import contextmanager
from typing import List, Optional

from . import chipguard, counters

CONTROLS = ("fault-plan", "lane-flip", "truth-shift")


def make_verifier(config: dict):
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    return TpuSecpVerifier(**config["verifier"])


def fresh_caches(config: dict):
    from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache

    c = config["caches"]
    return SigCache(int(c["sig_entries"])), ScriptExecutionCache(int(c["script_entries"]))


class PathWatch:
    """Holds a window to the chip path: every dispatch on the expected
    backend, no new shape (so nothing compiled), no retry, demotion,
    containment, host-exact lane or guard anomaly, the ladder on its top
    rung."""

    def __init__(self, verifier, backend: str):
        self.verifier = verifier
        self.backend = backend
        self.since = chipguard.fallback_counters()
        self.before: Optional[dict] = None
        self.after: Optional[dict] = None

    def open(self) -> None:
        self.before = counters.snapshot()

    def close(self) -> None:
        self.after = counters.snapshot()

    def problems(self) -> List[str]:
        out: List[str] = []
        by_backend = counters.rose_by_label(
            self.before, self.after, "consensus_dispatch_total", "backend"
        )
        if not by_backend.get(self.backend):
            out.append(f"no {self.backend!r} dispatch in the window: {by_backend}")
        other = {k: v for k, v in by_backend.items() if k != self.backend}
        if other:
            out.append(f"dispatches off the {self.backend!r} rung: {other}")
        new = counters.rose(self.before, self.after, "consensus_dispatch_new_shapes_total")
        if new:
            out.append(f"{new:g} new padded shape(s) inside the window: something compiled")
        try:
            chipguard.assert_clean(self.verifier, "run", self.since)
        except chipguard.ChipPathError as e:
            out.append(str(e))
        return out


def lane_flipper(verifier, after_s: float = 0.5):
    """Control: after the guards have passed a chunk, invert its real
    lanes' verdicts where the verifier hands them to the driver. (One
    flipped lane can be a CHECKMULTISIG pairing that no verdict hangs on; a
    chunk cannot.) It is the first chunk settled `after_s` into the window,
    so that its requests were due inside it. Returns the undo."""
    import time

    import numpy as np

    real = verifier.sync_lanes
    state = {"armed": True, "from": time.monotonic() + after_s}

    def broken(pending, n):
        ok, needs = real(pending, n)
        if state["armed"] and n and time.monotonic() >= state["from"]:
            state["armed"] = False
            ok = ~np.asarray(ok, dtype=bool)
        return ok, needs

    verifier.sync_lanes = broken
    return lambda: setattr(verifier, "sync_lanes", real)


@contextmanager
def armed(control: Optional[str], verifier, seed: int):
    """The window under `control` (one of `CONTROLS` that breaks the timed
    path; `truth-shift` is the drivers' own), or untouched for None."""
    if control == "lane-flip":
        undo = lane_flipper(verifier)
        try:
            yield
        finally:
            undo()
    elif control == "fault-plan":
        with fault_plan(seed):
            yield
    else:
        yield


def fault_plan(seed: int):
    """Control: the program's own seeded fault plan, one flipped verdict at
    the settle seam. The guards catch it, so verdicts stay right and the
    run is no longer a clean run of the chip path."""
    from bitcoinconsensus_tpu.resilience import faults

    plan = faults.FaultPlan([faults.FaultSpec("jax_backend.verdict", "flip", count=1)])
    return faults.inject(plan, seed=seed % (1 << 31))
