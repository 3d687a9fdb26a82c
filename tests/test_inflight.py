"""In-flight dispatch queue: deadlines, ordering, backpressure, requeue.

Unit coverage of `resilience/inflight.py` (the queue driven through stub
callbacks, so every policy edge is exercised without XLA) plus
end-to-end overlap through `TpuSecpVerifier.verify_checks_begin/finish`
with the host-exact stand-in kernel from test_resilience. The REAL
kernels go through the same seam in `scripts/consensus_chaos.py`'s
async leg and CI's chaos-smoke job.

The async contract: overlap may reorder *settlement*, never verdicts —
every ticket still resolves through the verdict guards or falls closed
to the host oracle (`outcome is None`).
"""

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.resilience import degrade as D
from bitcoinconsensus_tpu.resilience import guards as G
from bitcoinconsensus_tpu.resilience import inflight as I
from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject

from test_resilience import _checks, _stub_verifier


# ---------------------------------------------------------------------------
# Queue-level harness: stub backend, no JAX.


class _Backend:
    """Scriptable launch/materialize pair for driving the queue."""

    def __init__(self, launch_fails=0, settle_fails=0):
        self.launches = []       # (n, level) per (re)launch
        self.settles = []        # ticket.n per clean materialize
        self.launch_fails = launch_fails
        self.settle_fails = settle_fails

    def launch(self, args, n, level, sset=None):
        self.launches.append((n, level))
        if self.launch_fails > 0:
            self.launch_fails -= 1
            raise RuntimeError("injected launch failure")
        return ("dev", n, level), None

    def materialize(self, ticket):
        if self.settle_fails > 0:
            self.settle_fails -= 1
            raise G.VerdictAnomaly("test.inflight", "stub")
        ok = np.ones(ticket.n, dtype=bool)
        self.settles.append(ticket.n)
        return ok, np.zeros(ticket.n, dtype=bool), True


def _mk_queue(backend, levels=("stub", "host"), max_depth=4,
              deadline_s=8.0, **res_kw):
    res = D.DispatchResilience(list(levels), name="inflight-test", **res_kw)
    q = I.InflightQueue(
        res, "test.inflight", launch=backend.launch,
        materialize=backend.materialize, max_depth=max_depth,
        deadline_s=deadline_s, backoff_s=0.0,
    )
    return q, res


def test_dispatch_returns_unsettled_ticket_and_settle_is_idempotent():
    be = _Backend()
    q, _res = _mk_queue(be)
    t = q.dispatch(("args",), 5)
    assert not t.settled and q.depth == 1
    assert be.launches == [(5, "stub")]
    ok, needs = q.settle(t)
    assert t.settled and q.depth == 0
    assert ok.all() and not needs.any()
    # Re-settling returns the cached outcome without re-launching or
    # double-counting anything.
    assert q.settle(t) == (ok, needs)
    assert be.launches == [(5, "stub")]


def test_out_of_order_settlement():
    be = _Backend()
    q, res = _mk_queue(be)
    tickets = [q.dispatch(("a",), n) for n in (3, 4, 5)]
    assert q.depth == 3
    for t in reversed(tickets):
        ok, _needs = q.settle(t)
        assert ok.shape == (t.n,) and ok.all()
    assert q.depth == 0
    assert res.ladder.current == "stub"  # three clean settles, no demotion


def test_backpressure_settles_oldest_first():
    be = _Backend()
    q, _res = _mk_queue(be, max_depth=2)
    before = I._BACKPRESSURE.value(site="test.inflight")
    t0 = q.dispatch(("a",), 1)
    t1 = q.dispatch(("a",), 2)
    t2 = q.dispatch(("a",), 3)
    assert t0.settled and not t1.settled and not t2.settled
    assert q.depth == 2
    assert I._BACKPRESSURE.value(site="test.inflight") == before + 1
    assert be.settles[0] == 1  # the oldest ticket paid the backpressure
    q.drain()
    assert q.depth == 0


def test_deadline_expiry_mid_queue_contains_without_retry():
    be = _Backend(settle_fails=99)
    q, res = _mk_queue(be, deadline_s=0.0, demote_after=5)
    expired0 = I._DEADLINE_EXPIRED.value(site="test.inflight")
    contained0 = G.CONTAINED.value(site="test.inflight")
    lanes0 = G.HOST_EXACT_LANES.value()
    tickets = [q.dispatch(("a",), 7), q.dispatch(("a",), 9)]
    for t in tickets:
        assert q.settle(t) is None  # fail-closed: host must re-verify
        assert t.attempts == 1      # expired deadline forbids retries
    assert I._DEADLINE_EXPIRED.value(site="test.inflight") == expired0 + 2
    assert G.CONTAINED.value(site="test.inflight") == contained0 + 2
    assert G.HOST_EXACT_LANES.value() == lanes0 + 16
    # Two consecutive failures sit under demote_after=5: no demotion —
    # deadline expiry contains the ticket without convicting the level.
    assert res.ladder.current == "stub"


def test_settle_retries_transient_failure_then_succeeds():
    be = _Backend(settle_fails=1)
    q, res = _mk_queue(be)
    t = q.dispatch(("a",), 4)
    fails0 = I._FAILURES.value(
        site="test.inflight", level="stub", exc="VerdictAnomaly")
    assert q.last_failure is None
    ok, _needs = q.settle(t)
    assert ok.all() and t.attempts == 2
    assert be.launches == [(4, "stub"), (4, "stub")]  # relaunched once
    assert res.ladder.current == "stub"
    # The retry absorbed the fault; its reason is kept, and counted.
    assert q.last_failure["stage"] == "settle"
    assert q.last_failure["exc"] == "VerdictAnomaly"
    assert I._FAILURES.value(
        site="test.inflight", level="stub", exc="VerdictAnomaly"
    ) == fails0 + 1


def test_launch_exception_is_a_settle_failure():
    be = _Backend(launch_fails=1)
    q, _res = _mk_queue(be)
    t = q.dispatch(("a",), 4)
    assert t.error is not None  # captured, not raised, at dispatch time
    ok, _needs = q.settle(t)
    assert ok.all() and t.attempts == 2
    assert q.last_failure == {
        "stage": "launch", "level": "stub", "lanes": 4, "shape": None,
        "attempt": 1, "exc": "RuntimeError",
        "error": "injected launch failure",
    }


def test_quarantine_cancels_and_redispatches_queued_tickets():
    be = _Backend(settle_fails=99)
    q, res = _mk_queue(be, demote_after=2)
    redisp0 = I._REDISPATCH.value(site="test.inflight")
    bad = q.dispatch(("a",), 3)
    queued = q.dispatch(("a",), 5)
    assert queued.level == "stub"
    assert q.settle(bad) is None          # exhausts retries, demotes
    assert res.ladder.current == "host"
    # The still-queued ticket was cancelled off the convicted level and
    # re-issued at the current rung, so it can never settle against a
    # backend the ladder has quarantined (nor re-promote it).
    assert I._REDISPATCH.value(site="test.inflight") == redisp0 + 1
    assert queued.level == D.HOST_LEVEL
    assert q.settle(queued) is None       # host rung: fail-closed outcome


# ---------------------------------------------------------------------------
# End-to-end: overlap through the verifier's begin/finish seam.


def test_begin_finish_overlap_matches_oracle():
    checks = _checks(13)
    v, oracle, state = _stub_verifier(checks)
    h1 = v.verify_checks_begin(checks)
    h2 = v.verify_checks_begin(checks)
    assert v._inflight.depth >= 1  # batch 2 dispatched while 1 in flight
    out1 = np.asarray(v.verify_checks_finish(h1), dtype=bool)
    out2 = np.asarray(v.verify_checks_finish(h2), dtype=bool)
    assert np.array_equal(out1, oracle) and np.array_equal(out2, oracle)
    assert v._inflight.depth == 0


def test_begin_finish_out_of_order():
    checks = _checks(6)
    v, oracle, _state = _stub_verifier(checks)
    h1 = v.verify_checks_begin(checks)
    h2 = v.verify_checks_begin(checks)
    out2 = np.asarray(v.verify_checks_finish(h2), dtype=bool)
    out1 = np.asarray(v.verify_checks_finish(h1), dtype=bool)
    assert np.array_equal(out1, oracle) and np.array_equal(out2, oracle)


def test_overlap_with_flip_fault_stays_bit_identical():
    checks = _checks(13)
    v, oracle, _state = _stub_verifier(checks)
    plan = FaultPlan([FaultSpec("jax_backend.verdict", "flip")])
    with inject(plan, seed=11) as inj:
        h1 = v.verify_checks_begin(checks)
        h2 = v.verify_checks_begin(checks)
        out1 = np.asarray(v.verify_checks_finish(h1), dtype=bool)
        out2 = np.asarray(v.verify_checks_finish(h2), dtype=bool)
    assert inj.total_fired() >= 1
    assert np.array_equal(out1, oracle) and np.array_equal(out2, oracle)


def test_backpressure_bounds_depth_under_many_begins():
    checks = _checks(3, bad_last=False)
    v, oracle, _state = _stub_verifier(checks)
    v._inflight.max_depth = 2
    handles = [v.verify_checks_begin(checks) for _ in range(6)]
    assert v._inflight.depth <= 2
    for h in handles:
        out = np.asarray(v.verify_checks_finish(h), dtype=bool)
        assert np.array_equal(out, oracle)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(4))
def test_async_chaos_soak(seed):
    """Multi-seed soak: every catchable fault class injected while two
    batches overlap the async seam; verdicts must stay bit-identical."""
    checks = _checks(13)
    kinds = [("jax_backend.verdict", k)
             for k in ("invert", "flip", "value", "nan", "garbage", "shape")]
    kinds += [("jax_backend.dispatch", k) for k in ("raise", "timeout")]
    for site, kind in kinds:
        v, oracle, _state = _stub_verifier(checks)
        with inject(FaultPlan([FaultSpec(site, kind)]), seed=seed) as inj:
            h1 = v.verify_checks_begin(checks)
            h2 = v.verify_checks_begin(checks)
            out1 = np.asarray(v.verify_checks_finish(h1), dtype=bool)
            out2 = np.asarray(v.verify_checks_finish(h2), dtype=bool)
        assert inj.total_fired() >= 1, (site, kind)
        assert np.array_equal(out1, oracle), (site, kind, seed)
        assert np.array_equal(out2, oracle), (site, kind, seed)
