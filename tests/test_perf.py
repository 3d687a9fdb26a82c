"""Performance observatory: phase timelines on in-flight tickets, the
overlap-efficiency gauge, provenance-gated report comparison, and the
disarmed-path overhead budget.

The contract (README "Performance observatory"): every guarded dispatch
ticket carries a PhaseTimeline whose settle feeds
`consensus_pipeline_phase_seconds{phase=...}`; reports are only ever
compared when their provenance matches; and with
BITCOINCONSENSUS_TPU_PERF_TIMELINE=0 the stamp hooks cost < 1% of a
small verify (event-cost accounting, not a flaky wall A/B).
"""

import threading
import time

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.obs import get_registry, span
from bitcoinconsensus_tpu.obs import perf as P

from test_inflight import _Backend, _mk_queue


# ---------------------------------------------------------------------------
# PhaseTimeline unit semantics.


def _phase_count(phase):
    h = get_registry().get("consensus_pipeline_phase_seconds")
    for s in h._samples():
        if s["labels"] == {"phase": phase}:
            return s["count"]
    return 0


def test_timeline_stamps_feed_phase_histograms():
    before = {p: _phase_count(p) for p in
              ("prepare", "launch", "inflight", "settle", "total")}
    tl = P.PhaseTimeline()
    for name in ("submit", "prepare", "launch"):
        tl.stamp(name)
    tl.stamp_once("first_poll")
    tl.stamp_once("first_poll")  # must not move the first-poll edge
    tl.stamp("settle_start")
    tl.stamp("settle_end")
    phases = tl.phase_seconds()
    assert set(phases) == {"prepare", "launch", "inflight", "settle", "total"}
    assert all(v >= 0 for v in phases.values())
    assert phases["total"] >= phases["settle"]
    tl.finalize()
    tl.finalize()  # idempotent: one observation per phase, not two
    for p, n in before.items():
        assert _phase_count(p) == n + 1


def test_timeline_shard_stamps():
    before = _phase_count("shard_check")
    tl = P.PhaseTimeline()
    tl.stamp("settle_start")
    tl.stamp_shard(0)
    tl.stamp_shard(1)
    tl.stamp_shard(2)
    tl.stamp("settle_end")
    tl.finalize()
    assert _phase_count("shard_check") == before + 3


def test_overlap_efficiency_math():
    """hidden/wire over the window: a ticket polled at launch hides
    nothing; one polled at settle hides everything."""
    P.reset_overlap_window()
    tl = P.PhaseTimeline()
    t0 = 100.0
    tl.stamps = {"submit": t0, "prepare": t0, "launch": t0,
                 "first_poll": t0 + 0.08, "settle_start": t0 + 0.09,
                 "settle_end": t0 + 0.10}
    tl.finalize()
    assert P.overlap_efficiency() == pytest.approx(0.8)
    tl2 = P.PhaseTimeline()
    tl2.stamps = {"submit": t0, "launch": t0, "first_poll": t0,
                  "settle_start": t0 + 0.09, "settle_end": t0 + 0.10}
    tl2.finalize()
    # window-weighted: (0.08 + 0.0) / (0.10 + 0.10)
    assert P.overlap_efficiency() == pytest.approx(0.4)
    P.reset_overlap_window()


def test_null_timeline_is_inert_singleton():
    import os

    assert P.new_timeline() is not P.NULL_TIMELINE  # armed by default
    P.set_enabled(False)
    try:
        tl = P.new_timeline(trace=123)
        assert tl is P.NULL_TIMELINE
        assert tl.trace is None
        tl.stamp("submit")
        tl.stamp_once("first_poll")
        tl.stamp_shard(0)
        tl.finalize()
        assert tl.phase_seconds() == {}
    finally:
        P.set_enabled(True)
    assert os.environ.get("BITCOINCONSENSUS_TPU_PERF_TIMELINE", "") not in (
        "0", "off",
    ), "suite expects timelines armed"


# ---------------------------------------------------------------------------
# Queue integration: every dispatched ticket times its lifecycle.


def test_ticket_timeline_through_queue_settle():
    be = _Backend()
    q, _res = _mk_queue(be)
    before = _phase_count("total")
    t = q.dispatch(("args",), 5)
    assert "submit" in t.timeline.stamps and "launch" in t.timeline.stamps
    q.settle(t)
    assert _phase_count("total") == before + 1
    ph = t.timeline.phase_seconds()
    assert ph["total"] >= ph["inflight"] >= 0


def test_ticket_timeline_adopts_current_trace():
    be = _Backend()
    q, _res = _mk_queue(be)
    with span("perf-trace-root") as sp:
        t = q.dispatch(("args",), 3)
        assert t.timeline.trace == sp.trace
    q.settle(t)
    t2 = q.dispatch(("args",), 3)  # outside any span: no trace
    assert t2.timeline.trace is None
    q.settle(t2)


# ---------------------------------------------------------------------------
# Provenance + report comparison (the CI regression gate).


def test_provenance_keys_and_comparability():
    prov = P.provenance(cmd="test")
    for key in ("platform", "device_kind", "jax", "jaxlib", "python",
                "git_rev", "cmd"):
        assert key in prov, key
    assert prov["cmd"] == "test"
    assert prov["platform"] == "cpu"  # conftest forces the CPU mesh
    ok, why = P.comparable(prov, dict(prov))
    assert ok and why == ""
    other = dict(prov, device_kind="TPU v5e")
    ok, why = P.comparable(prov, other)
    assert not ok and "device_kind" in why


def _report(mean_prepare_s, vps=1000.0, platform="cpu"):
    return {
        "workload": {"verifies_per_sec": vps},
        "phases": {
            "prepare": {"count": 4, "mean_s": mean_prepare_s,
                        "total_s": 4 * mean_prepare_s},
            "settle": {"count": 4, "mean_s": 0.002, "total_s": 0.008},
        },
        "provenance": {"platform": platform, "device_kind": platform},
    }


def test_compare_reports_catches_injected_prepare_slowdown():
    baseline = _report(0.004)
    slowed = _report(0.050)  # a 46 ms injected sleep, unmistakable
    problems = P.compare_reports(baseline, slowed, tolerance=0.5)
    assert problems and any("prepare" in p for p in problems)
    # Within tolerance (and the settle phase unchanged): clean pass.
    assert P.compare_reports(baseline, _report(0.005), tolerance=0.5) == []


def test_compare_reports_ignores_microsecond_noise():
    """The absolute floor: a 3x blowup on a 2us phase is scheduler
    noise, not a regression — the relative tolerance alone would flap."""
    baseline = _report(0.000002)
    noisy = _report(0.000006)
    assert P.compare_reports(baseline, noisy, tolerance=0.5) == []


def test_compare_reports_flags_throughput_drop():
    baseline = _report(0.004, vps=1000.0)
    slow = _report(0.004, vps=100.0)
    problems = P.compare_reports(baseline, slow, tolerance=0.5)
    assert problems and any("throughput" in p for p in problems)


def test_compare_reports_skips_on_provenance_mismatch():
    """A CPU container run must never fail a TPU baseline: comparison
    is refused (None), not failed."""
    tpu_baseline = _report(0.0001, vps=100000.0, platform="tpu")
    cpu_run = _report(0.050, vps=50.0, platform="cpu")
    assert P.compare_reports(tpu_baseline, cpu_run) is None


# ---------------------------------------------------------------------------
# Disarmed-path overhead: event-cost accounting against a stub workload.


@pytest.mark.usefixtures("warm_kernel")
def test_disarmed_stamp_overhead_under_one_percent():
    """With timelines disarmed, the per-ticket hook cost (new_timeline +
    8 no-op stamps, all priced by microbenchmark) must stay under 1% of
    a small real verify_batch — event-cost accounting, mirroring the
    no-sink budget test, instead of a flaky wall A/B."""
    from bitcoinconsensus_tpu.models.batch import verify_batch
    from bitcoinconsensus_tpu.models.sigcache import (
        ScriptExecutionCache,
        SigCache,
    )

    from test_obs import _make_items

    items = _make_items(8)

    def run():
        res = verify_batch(
            items,
            sig_cache=SigCache(cache_label="perf-ovh"),
            script_cache=ScriptExecutionCache(cache_label="perf-ovh-s"),
        )
        assert all(r.ok for r in res)

    run()  # warm the jit/compile caches

    tickets_before = get_registry().get(
        "consensus_inflight_tickets_total"
    )._samples()
    total0 = sum(s["value"] for s in tickets_before)
    P.set_enabled(False)
    try:
        wall = min(_timed(run) for _ in range(3))

        nt = P.NULL_TIMELINE
        reps = 100_000
        per_stamp = _timed(
            lambda: [nt.stamp("x") for _ in range(reps)]
        ) / reps
        per_new = _timed(
            lambda: [P.new_timeline() for _ in range(reps)]
        ) / reps
    finally:
        P.set_enabled(True)
    total1 = sum(
        s["value"]
        for s in get_registry().get(
            "consensus_inflight_tickets_total"
        )._samples()
    )
    # Tickets per timed run (3 disarmed runs above); every ticket costs
    # new_timeline + at most 8 hook calls (6 lifecycle stamps,
    # stamp_once, finalize); this non-mesh path takes no shard stamps.
    tickets_per_run = max(1, (total1 - total0) // 3)
    bound = tickets_per_run * (8 * per_stamp + per_new)
    assert bound < 0.01 * wall, (
        f"disarmed hook bound {bound * 1e6:.2f}us exceeds 1% of "
        f"verify_batch wall {wall * 1e3:.2f}ms "
        f"({tickets_per_run} tickets/run)"
    )


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# The overlap gauge is thread-safe (tickets settle from worker threads).


def test_overlap_window_threaded():
    P.reset_overlap_window()
    n_threads, iters = 4, 50
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for _ in range(iters):
            tl = P.PhaseTimeline()
            tl.stamps = {"submit": 0.0, "launch": 0.0, "first_poll": 0.5,
                         "settle_start": 0.9, "settle_end": 1.0}
            tl.finalize()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert P.overlap_efficiency() == pytest.approx(0.5)
    P.reset_overlap_window()
