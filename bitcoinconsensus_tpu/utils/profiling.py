"""Per-phase wall-clock timers — a thin adapter over the obs telemetry.

`verifier.phases` (a `Phases`) is the one phase clock of the verify path:
every per-layer phase metric of the benchmark reads it and nothing else
times those seams.

Historically `Phases` owned its own perf_counter pairs and bare dicts;
the dict read-modify-writes raced under the `_idx_threads()` worker pool
in `models/batch.py` (two threads could each read `_calls["x"] == 3` and
both write 4). It is now a facade over `bitcoinconsensus_tpu.obs`:

- each phase runs inside an obs span named ``<scope>.<name>`` — so every
  `Phases` user feeds the global metrics registry
  (`consensus_span_duration_seconds{span="verifier.dispatch"}` etc.) and
  any attached JSONL sink for free;
- the per-instance accumulation that `report()`/`total()` serve is kept,
  but under a lock (regression-tested by tests/test_obs.py hammering one
  instance from many threads).

Usage is unchanged:
    ph = Phases()
    with ph("prep"):
        ...
    ph.report()  # {"prep": {"secs": ..., "calls": ..., "outer_secs": ...}, ...}

Phases nest (the mesh verifier's `shard_put` runs inside `dispatch`), so
the `secs` of a report overlap. `outer_secs` is the part of `secs` a phase
spent as the outermost open phase of this instance on its thread: over a
call made on one thread the `outer_secs` tile it without overlap, and
wall - sum(outer_secs) is the time no phase names.

`Phases(enabled=False)` turns them into no-ops. `reset()` clears only the
instance's dicts — the cumulative registry metrics are process-global by
design (reset those via obs.get_registry().reset()).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict

from ..obs import spans as _spans

__all__ = ["Phases", "phases_of"]


class Phases:
    def __init__(self, enabled: bool = True, scope: str = "verifier"):
        self.enabled = enabled
        self.scope = scope
        self._lock = threading.Lock()
        self._secs: Dict[str, float] = {}
        self._calls: Dict[str, int] = {}
        self._outer: Dict[str, float] = {}
        self._open = threading.local()  # .depth: phases open on this thread

    @contextmanager
    def __call__(self, name: str):
        if not self.enabled:
            yield
            return
        sp = None
        depth = getattr(self._open, "depth", 0)
        self._open.depth = depth + 1
        try:
            with _spans.span(f"{self.scope}.{name}") as sp:
                yield
        finally:
            self._open.depth = depth
            if sp is not None and sp.duration_s is not None:
                with self._lock:
                    self._secs[name] = self._secs.get(name, 0.0) + sp.duration_s
                    self._calls[name] = self._calls.get(name, 0) + 1
                    if depth == 0:
                        self._outer[name] = self._outer.get(name, 0.0) + sp.duration_s

    def reset(self) -> None:
        with self._lock:
            self._secs.clear()
            self._calls.clear()
            self._outer.clear()

    def report(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                k: {"secs": round(self._secs[k], 6), "calls": self._calls[k],
                    "outer_secs": round(self._outer.get(k, 0.0), 6)}
                for k in self._secs
            }

    def total(self) -> float:
        with self._lock:
            return sum(self._secs.values())


_OFF = Phases(enabled=False)


def phases_of(verifier) -> Phases:
    """`verifier.phases`, the one phase clock of the verify path; a clock
    that times nothing for None or a stand-in verifier (anything with
    `verify_checks`), so a driver never asks which it has."""
    return getattr(verifier, "phases", _OFF)
