"""In-flight dispatch tickets with fail-closed settlement.

JAX arrays are futures: a dispatched verify batch is already
asynchronous until something on the host materializes it. PR 5's
dispatch/settle seam blocked immediately after every launch, which made
containment easy but serialized the pipeline. This module makes the seam
asynchronous *without* loosening it: every dispatch returns a
:class:`Ticket` and every ticket still settles through the verdict
guards, the bounded-retry budget, and the degradation ladder before any
verdict is believed.

Ticket lifecycle::

    dispatch(args, n)                      settle(ticket)
      │ backpressure: settle oldest         │ materialize → guards
      │   while depth ≥ max_depth           │   (validate / sentinels /
      │ pick ladder level                   │    checksum) on the host
      │ prepare(args, n)  → sentinels       │ ok → report(level, True),
      │ launch(args, n, level) → futures    │      latency observed, done
      │ deadline = now + deadline_s         │ fail → report(level, False);
      └ append to queue ──────────────────▶ │   deadline expired → host
                                            │   else retry/backoff,
                                            │   re-pick level, relaunch
                                            │ terminal → CONTAINED,
                                            │   host-exact lanes, None

A `None` outcome is the fail-closed signal: the caller must re-verify
the ticket's lanes on the exact host oracle. When a settle failure
demotes the ladder, every still-queued ticket sitting on a now-
quarantined level is *cancelled and re-dispatched* at the new level
(counted in ``consensus_inflight_redispatch_total``) so queued work
never settles against a backend the ladder has already convicted.

Backpressure: the queue holds at most ``max_depth`` unsettled tickets;
a dispatch beyond that settles the oldest first (counted). A stalled
device therefore degrades to synchronous-with-retries instead of
accumulating unbounded host state.

``settle_array`` is the one sanctioned host materialization outside the
settle seam — `analysis/host_lint.py`'s sync rule bans bare
``np.asarray`` / ``block_until_ready`` on the dispatch path everywhere
else, so overlap cannot silently rot back into blocking code.

Host-side policy only: nothing here is traced, and time is read through
the sanctioned ``obs`` clock.
"""

from __future__ import annotations

import time
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from ..obs import counter as _obs_counter
from ..obs import flight as _flight
from ..obs import gauge as _obs_gauge
from ..obs import histogram as _obs_histogram
from ..obs import monotonic as _monotonic
from . import guards as _guards
from .degrade import HOST_LEVEL, DispatchResilience

__all__ = ["InflightQueue", "Ticket", "settle_array"]

_DEPTH = _obs_gauge(
    "consensus_inflight_depth",
    "unsettled tickets currently in the dispatch queue, by site",
    ("site",),
)
_TICKETS = _obs_counter(
    "consensus_inflight_tickets_total",
    "tickets dispatched through the in-flight queue, by site",
    ("site",),
)
_SETTLE_SECONDS = _obs_histogram(
    "consensus_inflight_settle_seconds",
    "wall-clock time from dispatch to settled verdict per ticket",
    buckets=(0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0),
)
_DEADLINE_EXPIRED = _obs_counter(
    "consensus_inflight_deadline_expired_total",
    "tickets whose wall-clock deadline expired before a clean settle "
    "(demoted straight to the host oracle), by site",
    ("site",),
)
_REDISPATCH = _obs_counter(
    "consensus_inflight_redispatch_total",
    "queued tickets cancelled and re-dispatched after a ladder "
    "quarantine invalidated their level, by site",
    ("site",),
)
_BACKPRESSURE = _obs_counter(
    "consensus_inflight_backpressure_total",
    "dispatches that had to settle the oldest ticket first because the "
    "queue was at max depth, by site",
    ("site",),
)
_FAILURES = _obs_counter(
    "consensus_inflight_failures_total",
    "launch/settle attempts that failed (each is a ladder failure), by "
    "site, ladder level and exception class",
    ("site", "level", "exc"),
)


def settle_array(x) -> np.ndarray:
    """THE sanctioned device→host materialization outside the settle seam.

    Forces (and waits for) the value of an in-flight array. Every
    synchronization on the dispatch path must flow through here or
    through the settle seam itself (`_materialize_guarded`) — the
    host_lint sync rule keeps it that way. Centralizing the block point
    is what makes "the pipeline overlaps" a checkable property instead
    of a hope.
    """
    from ..ops.regions import region_scope

    with region_scope("settle"):
        return np.asarray(x)


class Ticket:
    """One in-flight dispatch: unsynchronized result + settle context."""

    __slots__ = (
        "args", "n", "level", "probe", "attempts", "born", "deadline",
        "sset", "result", "aux", "error", "settled", "outcome", "seq",
    )

    def __init__(self, args, n: int, level: str, probe: bool,
                 deadline: float, born: float, seq: int):
        self.args = args
        self.n = n                  # real (padded) lane count dispatched
        self.level = level          # ladder level the launch ran at
        self.probe = probe
        self.attempts = 1
        self.born = born
        self.deadline = deadline    # wall-clock settle deadline
        self.sset = None            # SentinelSet installed at prepare
        self.result = None          # unsynchronized device result
        self.aux = None             # what else the settle pulls (None: one result)
        self.error = None           # launch exception, if any
        self.settled = False
        self.outcome = None         # (ok, needs) after settle; None=host
        self.seq = seq


class InflightQueue:
    """Bounded queue of in-flight tickets settling through the guards.

    The queue owns *policy* (deadlines, retries, backpressure, ladder
    bookkeeping, re-dispatch after quarantine); the verifier supplies
    *mechanism* via callbacks:

    - ``prepare(args, n) -> (args, sset)`` — runs once per ticket at
      dispatch time: the lanes packed into the one buffer that travels
      (out of buffers that may be read-only), sentinel lanes installed.
    - ``launch(args, n, level, sset) -> (result, aux)`` — start the
      device work; returns the unsynchronized result, its host copy
      already asked for, plus whatever else the settle must pull (None
      where the checksum pair rides inside the result, as both verifiers'
      do). `sset` is whatever `prepare` returned
      (sentinel set or the sharded verifier's shard layout), so a launch
      can route by how the batch was laid out. Must not block.
      Exceptions are captured on the ticket and handled at settle (a
      launch failure is a settle failure that costs zero wire time).
    - ``materialize(ticket) -> (ok, needs, all_ok)`` — the settle seam:
      synchronize, run fault hooks, validate, check sentinels and the
      checksum. Raises ``VerdictAnomaly`` (or anything) on a bad buffer.
    - ``on_device(ticket, ok, needs, all_ok)`` — success accounting hook
      (verdict metrics); runs exactly once per cleanly settled ticket.
    """

    def __init__(
        self,
        resilience: DispatchResilience,
        site: str,
        launch: Callable[[Any, int, str, Any], Tuple[Any, Any]],
        materialize: Callable[[Ticket], Tuple[np.ndarray, Optional[np.ndarray], bool]],
        prepare: Optional[Callable[[Any, int], Tuple[Any, Any]]] = None,
        on_device: Optional[Callable[..., None]] = None,
        max_depth: int = 4,
        deadline_s: float = 8.0,
        backoff_s: float = 0.002,
    ):
        self._res = resilience
        self.site = site
        self._launch_cb = launch
        self._materialize = materialize
        self._prepare = prepare
        self._on_device = on_device
        self.max_depth = max(1, int(max_depth))
        self.deadline_s = float(deadline_s)
        self.backoff_s = float(backoff_s)
        self._pending: List[Ticket] = []
        self._seq = 0
        # Most recent launch/settle failure (see `_note_failure`): the
        # ladder absorbs the fault, this keeps its reason readable.
        self.last_failure: Optional[dict] = None

    # -- dispatch side -------------------------------------------------

    @property
    def full(self) -> bool:
        """At `max_depth`: the next dispatch settles the oldest ticket first."""
        return len(self._pending) >= self.max_depth

    def make_room(self) -> None:
        """Backpressure: settle the oldest tickets until one more fits. A
        caller that times the wait calls this before `dispatch` (the
        verifier's `backpressure` phase); `dispatch` then finds room."""
        while self.full:
            _BACKPRESSURE.inc(site=self.site)
            self.settle(self._pending[0])

    def dispatch(self, args, n: int) -> Ticket:
        """Launch one batch; return its ticket without synchronizing."""
        self.make_room()
        if self._prepare is not None:
            args, sset = self._prepare(args, n)
        else:
            sset = None
        level, probe = self._res.ladder.pick_level()
        now = _monotonic()
        ticket = Ticket(args, n, level, probe,
                        deadline=now + self.deadline_s, born=now,
                        seq=self._seq)
        self._seq += 1
        ticket.sset = sset
        _TICKETS.inc(site=self.site)
        self._launch(ticket)
        self._pending.append(ticket)
        _DEPTH.set(len(self._pending), site=self.site)
        return ticket

    def _launch(self, ticket: Ticket) -> None:
        """(Re)issue the device work for a ticket at its current level."""
        ticket.result = None
        ticket.aux = None
        ticket.error = None
        if ticket.level == HOST_LEVEL:
            return
        try:
            ticket.result, ticket.aux = self._launch_cb(
                ticket.args, ticket.n, ticket.level, ticket.sset
            )
        except Exception as exc:  # settled as a dispatch failure
            ticket.error = exc

    def _note_failure(self, ticket: Ticket, stage: str,
                      exc: BaseException) -> None:
        """Keep the reason for a failed attempt: counted by exception
        class, recorded in the flight ring, and held as `last_failure`
        (the retry/ladder policy below would otherwise discard it — a
        compiler error and a flipped lane look identical from outside)."""
        shape = getattr(ticket.args[0], "shape", None) if ticket.args else None
        self.last_failure = {
            "stage": stage,
            "level": ticket.level,
            "lanes": ticket.n,
            "shape": None if shape is None else tuple(shape),
            "attempt": ticket.attempts,
            "exc": type(exc).__name__,
            "error": str(exc),
        }
        _FAILURES.inc(site=self.site, level=ticket.level,
                      exc=type(exc).__name__)
        _flight.record("inflight.failure", site=self.site,
                       **self.last_failure)

    # -- settle side ---------------------------------------------------

    def settle(self, ticket: Ticket):
        """Resolve a ticket to `(ok, needs)` or None (host containment).

        Idempotent and order-independent: settling out of queue order is
        fine, and re-settling returns the cached outcome without
        re-touching the ladder or the containment counters.
        """
        if ticket.settled:
            return ticket.outcome
        try:
            self._pending.remove(ticket)
        except ValueError:
            pass
        _DEPTH.set(len(self._pending), site=self.site)
        res = self._res
        ladder = res.ladder
        start_idx = ladder.levels.index(ladder.current)
        outcome = None
        while ticket.level != HOST_LEVEL:
            if ticket.error is not None:
                self._note_failure(ticket, "launch", ticket.error)
            else:
                try:
                    ok, needs, all_ok = self._materialize(ticket)
                except Exception as exc:
                    self._note_failure(ticket, "settle", exc)
                else:
                    ladder.report(ticket.level, True, probe=ticket.probe)
                    _SETTLE_SECONDS.observe(_monotonic() - ticket.born)
                    if self._on_device is not None:
                        self._on_device(ticket, ok, needs, all_ok)
                    outcome = (ok, needs)
                    break
            ladder.report(ticket.level, False, probe=ticket.probe)
            if _monotonic() >= ticket.deadline:
                _DEADLINE_EXPIRED.inc(site=self.site)
                _flight.record("inflight.deadline_expired", site=self.site,
                               attempts=ticket.attempts, level=ticket.level)
                break
            if not res.may_retry(ticket.attempts, ticket.deadline, self.site):
                break
            ticket.attempts += 1
            if self.backoff_s > 0.0:
                time.sleep(min(self.backoff_s * (1 << min(ticket.attempts, 8)),
                               0.05))
            ticket.level, ticket.probe = ladder.pick_level()
            if ticket.level == HOST_LEVEL:
                break
            self._launch(ticket)
        if outcome is None:
            _guards.CONTAINED.inc(site=self.site)
            _guards.HOST_EXACT_LANES.inc(ticket.n)
            if ladder.current == HOST_LEVEL:
                ladder.report(HOST_LEVEL, True)
        ticket.settled = True
        ticket.outcome = outcome
        if ladder.levels.index(ladder.current) > start_idx:
            self._requeue_stale()
        return outcome

    def _requeue_stale(self) -> None:
        """Cancel + re-dispatch queued tickets on quarantined levels.

        After a demotion, an unsettled ticket launched at a higher rung
        would settle against a backend the ladder just convicted — and a
        clean settle there would *re-promote* the ladder, fighting the
        quarantine. Re-issue them at the current rung instead.
        """
        ladder = self._res.ladder
        cur = ladder.levels.index(ladder.current)
        for ticket in self._pending:
            if ticket.level == HOST_LEVEL:
                continue
            try:
                idx = ladder.levels.index(ticket.level)
            except ValueError:
                idx = -1
            if idx < cur:
                _REDISPATCH.inc(site=self.site)
                ticket.level, ticket.probe = ladder.pick_level()
                self._launch(ticket)

    # -- introspection -------------------------------------------------

    @property
    def depth(self) -> int:
        return len(self._pending)

    def drain(self) -> None:
        """Settle everything still in flight (oldest first)."""
        while self._pending:
            self.settle(self._pending[0])
