"""Serving-layer tests: fair coalescing queue, SLO admission control,
overload shedding, graceful drain, and the bounded-retry client.

Unit tests drive the queue/shedding policy objects with injected clocks
and histograms (fully deterministic, no device work); the end-to-end
tests run a real `VerifyServer` over the CPU verifier and assert the
serving layer is a pure transport: verdicts bit-identical to a direct
`verify_batch`, sheds explicit (`Error.ERR_OVERLOADED`), shutdown
settling everything admitted.
"""

import threading
import types

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.api import Error
from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_LIBCONSENSUS
from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
from bitcoinconsensus_tpu.obs import get_registry
from bitcoinconsensus_tpu.obs.metrics import Histogram
from bitcoinconsensus_tpu.resilience.degrade import Ladder
from bitcoinconsensus_tpu.serving import (
    SHED_CLOSED,
    SHED_SLO,
    SHED_TENANT_FULL,
    AdmissionController,
    CoalescingQueue,
    OverloadError,
    QueueClosed,
    SloTracker,
    TenantQueueFull,
    VerifyServer,
    verify_with_retry,
)

from test_batch import make_p2wpkh_spend

# The end-to-end tests are about admission, drain and span parentage, not
# about the kernel: its first call is made before any of them waits.
pytestmark = pytest.mark.usefixtures("warm_kernel")


def _entry(tenant, enqueued=0.0):
    return types.SimpleNamespace(tenant=tenant, enqueued=enqueued)


def _items(n=4, bad_first=True):
    """n single-input BatchItems; item 0 corrupt when bad_first."""
    out = []
    for i in range(n):
        txb, spk, amt = make_p2wpkh_spend(
            f"serve-test-{i}", corrupt=(bad_first and i == 0)
        )
        out.append(BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                             spent_output_script=spk, amount=amt))
    return out


# -- Histogram.quantile (export-side estimate; admission reads the
# -- SloTracker sample window instead) --------------------------------


def test_histogram_quantile_empty_is_none():
    h = Histogram("t_serv_q_empty", buckets=(1.0, 2.0))
    assert h.quantile(0.5) is None
    assert h.quantile(0.99) is None


def test_histogram_quantile_upper_bucket_edge():
    """quantile() is a conservative upper estimate: it returns the edge
    of the first bucket whose cumulative count reaches the rank."""
    h = Histogram("t_serv_q_edges", buckets=(0.1, 1.0, 10.0))
    for _ in range(9):
        h.observe(0.05)   # bucket le=0.1
    h.observe(5.0)        # bucket le=10.0
    assert h.quantile(0.5) == 0.1
    assert h.quantile(0.9) == 0.1
    assert h.quantile(0.99) == 10.0
    assert h.quantile(1.0) == 10.0


def test_histogram_quantile_overflow_is_inf():
    import math

    h = Histogram("t_serv_q_inf", buckets=(0.1,))
    h.observe(99.0)  # lands in the +Inf bucket
    assert h.quantile(0.5) == math.inf


def test_histogram_quantile_rejects_bad_q():
    h = Histogram("t_serv_q_badq", buckets=(1.0,))
    for q in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError):
            h.quantile(q)


# -- CoalescingQueue --------------------------------------------------


def test_queue_pop_is_round_robin_fair():
    """A flooding tenant gets one entry per rotation turn: a1 a2 a3 then
    b1 c1 must pop as a1 b1 c1 (one per tenant), not a1 a2 a3."""
    q = CoalescingQueue(tenant_depth=8)
    for e in (_entry("a"), _entry("a"), _entry("a"),
              _entry("b"), _entry("c")):
        q.put(e)
    got = q.take(3, flush_s=0.0)
    assert [e.tenant for e in got] == ["a", "b", "c"]
    got = q.take(3, flush_s=0.0)
    assert [e.tenant for e in got] == ["a", "a"]
    assert q.total == 0


def test_queue_tenant_depth_bound():
    q = CoalescingQueue(tenant_depth=2)
    q.put(_entry("a"))
    q.put(_entry("a"))
    with pytest.raises(TenantQueueFull):
        q.put(_entry("a"))
    q.put(_entry("b"))  # other tenants unaffected
    assert q.total == 3 and q.depth("a") == 2 and q.depth("b") == 1


def test_queue_size_trigger_pops_immediately():
    q = CoalescingQueue(tenant_depth=8)
    q.put(_entry("a"))
    q.put(_entry("b"))
    # flush_s is huge but total >= max_n: must not wait.
    got = q.take(2, flush_s=3600.0)
    assert len(got) == 2


def test_queue_time_trigger_via_injected_clock():
    now = [100.0]
    q = CoalescingQueue(tenant_depth=8, clock=lambda: now[0])
    q.put(_entry("a", enqueued=100.0))
    now[0] = 100.2  # oldest has waited 0.2s > flush_s=0.1
    got = q.take(8, flush_s=0.1)
    assert len(got) == 1


def test_queue_nonblocking_take_returns_none_when_empty():
    q = CoalescingQueue(tenant_depth=8)
    assert q.take(8, flush_s=0.0, block=False) is None


def test_queue_close_drains_then_none_and_rejects_put():
    q = CoalescingQueue(tenant_depth=8)
    q.put(_entry("a"))
    q.close()
    with pytest.raises(QueueClosed):
        q.put(_entry("a"))
    assert len(q.take(8, flush_s=3600.0)) == 1  # drain flushes at once
    assert q.take(8, flush_s=3600.0) is None    # empty + closed


def test_queue_cancel_all_returns_everything():
    q = CoalescingQueue(tenant_depth=8)
    for e in (_entry("a"), _entry("a"), _entry("b")):
        q.put(e)
    cancelled = q.cancel_all()
    assert len(cancelled) == 3 and q.total == 0
    assert q.take(8, flush_s=0.0, block=False) is None


# -- SloTracker / AdmissionController ---------------------------------


def test_slo_tracker_publishes_quantile_gauges():
    """Quantiles are exact order statistics over the sample window (the
    histogram is an export sink only), published as gauges."""
    h = Histogram("t_serv_slo_gauges", buckets=(0.1, 0.5, 1.0))
    slo = SloTracker(histogram=h)
    for _ in range(50):
        slo.observe(0.05)
    for _ in range(50):
        slo.observe(0.7)
    assert slo.quantile(0.5) == 0.05
    assert slo.quantile(0.99) == 0.7
    g = get_registry().get("consensus_serving_slo_seconds")
    assert g.value(q="p50") == 0.05
    assert g.value(q="p99") == 0.7
    # The export histogram was fed every observation (its own quantile
    # stays the conservative bucket edge — export-only, never read back).
    assert h.quantile(0.5) == 0.1


def test_slo_tracker_window_ages_out_slow_tail():
    """A burst of slow batches (cold compile) must stop dominating p99
    once `window` fresh samples have settled — the recovery property
    the admission controller depends on."""
    slo = SloTracker(histogram=Histogram("t_serv_slo_window",
                                         buckets=(1.0,)), window=8)
    slo.observe(30.0)  # way past every bucket edge
    assert slo.quantile(0.99) == 30.0
    for _ in range(8):
        slo.observe(0.01)
    assert slo.quantile(0.99) == 0.01  # the 30s sample aged out


def test_slo_trackers_are_isolated_per_instance():
    """Two default trackers share only the export histogram: one slow
    instance's tail must not leak into the other's admission signal."""
    slow, fresh = SloTracker(), SloTracker()
    slow.observe(30.0)
    assert slow.quantile(0.99) == 30.0
    assert fresh.quantile(0.99) is None  # still cold
    with pytest.raises(ValueError):
        SloTracker(window=0)


def test_admission_cold_start_always_admits():
    slo = SloTracker(histogram=Histogram("t_serv_adm_cold",
                                         buckets=(1.0,)))
    adm = AdmissionController(0.001, batch_capacity=1, slo=slo)
    assert adm.admit(10**6) is None  # no latency evidence yet


def test_admission_sheds_on_projected_queue_wait():
    slo = SloTracker(histogram=Histogram("t_serv_adm_shed",
                                         buckets=(0.1, 0.5, 1.0)))
    for _ in range(50):
        slo.observe(0.5)  # p99 -> 0.5
    adm = AdmissionController(1.2, batch_capacity=8, slo=slo)
    # 4 ahead: 1 batch, 0.5s projected <= 1.2s budget -> admit.
    assert adm.admit(4) is None
    # 17 ahead: 3 batches, 1.5s projected > 1.2s -> shed.
    assert adm.admit(17) == SHED_SLO


def test_admission_empty_backlog_probes_through_slow_tail():
    """The no-recovery latch must be impossible: even when p99 dwarfs
    the budget (cold compile slower than the SLO), an empty backlog
    admits — that probe's settle is what refreshes the estimate."""
    slo = SloTracker(histogram=Histogram("t_serv_adm_probe",
                                         buckets=(1.0,)), window=4)
    slo.observe(30.0)  # one batch blew way past the 2s-style budget
    adm = AdmissionController(2.0, batch_capacity=8, slo=slo)
    assert adm.admit(1) == SHED_SLO   # anything ahead: shed
    assert adm.admit(0) is None       # nothing ahead: probe admitted
    for _ in range(4):
        slo.observe(0.01)             # probes settle fast; tail ages out
    assert adm.admit(17) is None      # full recovery, deep queue admits


def test_admission_quarantined_mesh_sheds_earlier():
    slo = SloTracker(histogram=Histogram("t_serv_adm_ladder",
                                         buckets=(0.1, 0.5, 1.0)))
    for _ in range(50):
        slo.observe(0.4)  # p99 -> 0.4
    ladder = Ladder(("pallas", "xla", "host"), "serv-adm-test")
    adm = AdmissionController(1.2, batch_capacity=8, slo=slo,
                              ladder=ladder)
    assert adm.deadline_budget_s() == 1.2
    assert adm.admit(8) is None  # 2 batches * 0.4 = 0.8 <= 1.2
    # Demote to the xla rung: budget halves, same depth now sheds.
    ladder.report("pallas", ok=False)
    ladder.report("pallas", ok=False)
    assert ladder.current == "xla"
    assert adm.deadline_budget_s() == pytest.approx(0.6)
    assert adm.admit(8) == SHED_SLO
    assert adm.admit(0) is None  # empty backlog still admitted


def test_admission_rejects_bad_config():
    slo = SloTracker(histogram=Histogram("t_serv_adm_cfg", buckets=(1.0,)))
    with pytest.raises(ValueError):
        AdmissionController(0.0, batch_capacity=8, slo=slo)
    with pytest.raises(ValueError):
        AdmissionController(1.0, batch_capacity=0, slo=slo)


# -- VerifyServer end to end ------------------------------------------


@pytest.fixture
def serve():
    """`serve(**config)` starts a VerifyServer; every server started is
    closed (drained; close is idempotent) when the test ends."""
    started = []

    def start(**config):
        started.append(VerifyServer(**config).start())
        return started[-1]

    yield start
    for srv in started:
        srv.close(drain=True)


@pytest.mark.slow
def test_server_concurrent_verdicts_bit_identical():
    """The serving layer is pure transport: concurrent multi-tenant
    submits must settle to verdicts identical to a direct verify_batch
    of the same items."""
    items = _items(6, bad_first=True)
    want = [(r.ok, r.error) for r in verify_batch(items)]

    results = [None] * len(items)

    with VerifyServer(max_batch=4, flush_s=0.005, tenant_depth=16) as srv:
        def client(i):
            res = srv.verify(items[i], tenant=f"t{i % 3}", timeout=120)
            results[i] = (res.ok, res.error)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(items))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
    assert results == want
    assert srv.pending == 0


def test_server_tenant_full_sheds_explicitly(serve):
    """tenant_depth=1 with a never-firing flush: the second submit from
    the same tenant must raise ERR_OVERLOADED immediately — an explicit
    reject, never a hang — while the queued request still settles on
    drain."""
    items = _items(2, bad_first=False)
    srv = serve(max_batch=64, flush_s=30.0, tenant_depth=1)
    queued = srv.submit(items[0])
    with pytest.raises(OverloadError) as ei:
        srv.submit(items[1])
    assert ei.value.code == Error.ERR_OVERLOADED
    assert ei.value.reason == SHED_TENANT_FULL
    srv.close(drain=True)
    assert queued.result(timeout=60).ok
    assert srv.pending == 0


def test_server_drain_settles_and_post_close_rejects(serve):
    items = _items(3, bad_first=False)
    srv = serve(max_batch=64, flush_s=30.0, tenant_depth=8)
    pend = [srv.submit(it) for it in items]
    assert not any(p.done() for p in pend)  # flush never fired
    srv.close(drain=True)  # drain trigger flushes + settles everything
    assert all(p.result(timeout=60).ok for p in pend)
    assert srv.pending == 0
    with pytest.raises(OverloadError) as ei:
        srv.submit(items[0])
    assert ei.value.reason == SHED_CLOSED
    srv.close()  # idempotent


def test_server_nondrain_close_cancels_explicitly(serve):
    items = _items(1, bad_first=False)
    srv = serve(max_batch=64, flush_s=30.0, tenant_depth=8)
    pend = srv.submit(items[0])
    srv.close(drain=False)
    with pytest.raises(OverloadError) as ei:
        pend.result(timeout=10)
    assert ei.value.reason == SHED_CLOSED
    assert srv.pending == 0


def test_server_worker_exception_fails_requests_explicitly(serve, monkeypatch):
    """A batch-driver crash must fail every windowed request with the
    exception — explicitly, not by leaving futures unresolved."""
    import bitcoinconsensus_tpu.serving.server as server_mod

    def boom(*a, **k):
        raise RuntimeError("driver crashed")
        yield  # pragma: no cover - makes this a generator function

    monkeypatch.setattr(server_mod, "verify_batch_stream", boom)
    items = _items(2, bad_first=False)
    srv = serve(max_batch=2, flush_s=0.001, tenant_depth=8)
    p0 = srv.submit(items[0])
    p1 = srv.submit(items[1])
    with pytest.raises(RuntimeError, match="driver crashed"):
        p0.result(timeout=30)
    with pytest.raises(RuntimeError, match="driver crashed"):
        p1.result(timeout=30)
    srv.close(drain=True)
    assert srv.pending == 0


def test_server_submit_before_start_rejects():
    srv = VerifyServer(max_batch=4, flush_s=0.005, tenant_depth=8)
    with pytest.raises(OverloadError) as ei:
        srv.submit(_items(1, bad_first=False)[0])
    assert ei.value.reason == SHED_CLOSED
    srv.close()  # close without start is a no-op


# -- bounded-retry client ---------------------------------------------


class _StubPending:
    def __init__(self, value):
        self._value = value

    def result(self, timeout=None):
        return self._value


class _StubServer:
    """Sheds the first `sheds` submits, then accepts."""

    def __init__(self, sheds):
        self.sheds = sheds
        self.calls = 0

    def submit(self, item, tenant="default"):
        self.calls += 1
        if self.calls <= self.sheds:
            raise OverloadError(SHED_SLO)
        return _StubPending(("ok", item, tenant))


def test_retry_client_recovers_after_sheds():
    import random

    srv = _StubServer(sheds=3)
    got = verify_with_retry(srv, "item", tenant="t0", retries=4,
                            backoff_s=0.001, max_backoff_s=0.002,
                            rng=random.Random(7))
    assert got == ("ok", "item", "t0")
    assert srv.calls == 4  # 3 sheds + 1 success


def test_retry_client_exhausted_budget_reraises():
    import random

    srv = _StubServer(sheds=100)
    with pytest.raises(OverloadError):
        verify_with_retry(srv, "item", retries=2, backoff_s=0.001,
                          max_backoff_s=0.002, rng=random.Random(7))
    assert srv.calls == 3  # initial + 2 retries


def test_retry_client_non_shed_errors_propagate():
    class _Broken:
        def submit(self, item, tenant="default"):
            raise ValueError("not a shed")

    with pytest.raises(ValueError):
        verify_with_retry(_Broken(), "item", retries=5, backoff_s=0.001)


# -- cross-thread trace stitching (the performance observatory's span
# -- contract: settle parents to submit across the worker thread) ------


def test_settle_span_parents_to_submit_span_across_worker_thread():
    """Every request's `serving.settle` span (emitted on the worker
    thread) must join the trace its `serving.submit` span rooted and
    parent directly to it — the JSONL tree no longer breaks at the
    thread boundary."""
    from bitcoinconsensus_tpu.obs import add_sink, remove_sink

    class _ListSink:
        def __init__(self):
            self.records = []

        def write(self, record):
            self.records.append(record)

    items = _items(3, bad_first=False)
    sink = _ListSink()
    add_sink(sink)
    try:
        with VerifyServer(max_batch=4, flush_s=0.005, tenant_depth=16) as srv:
            pend = [srv.submit(it, tenant=f"t{i}")
                    for i, it in enumerate(items)]
            assert all(p.result(timeout=120).ok for p in pend)
    finally:
        remove_sink(sink)

    submits = [r for r in sink.records if r["name"] == "serving.submit"]
    settles = [r for r in sink.records if r["name"] == "serving.settle"]
    assert len(submits) == len(items)
    assert len(settles) == len(items)
    by_span = {r["span_id"]: r for r in submits}
    for settle in settles:
        submit = by_span[settle["parent_id"]]  # parents to a submit span
        assert settle["trace"] == submit["trace"] == submit["span_id"]
        # settle really ran on the worker thread, not the submitter's
        assert settle["thread"] != submit["thread"]
        assert settle["attrs"]["tenant"] == submit["attrs"]["tenant"]
    # and the driver spans the burst emits join the burst leader's trace
    driver = [r for r in sink.records
              if r["name"].startswith("batch.stream_")]
    leader_traces = {r["trace"] for r in submits}
    assert driver and all(r["trace"] in leader_traces for r in driver)


# -- close() vs a concurrently-crashing worker (race-free shutdown) ----


def test_close_race_with_crashing_worker_settles_stranded_put(serve):
    """A submit racing a worker crash can land its request in the queue
    AFTER the dead worker's backstop drain swept it; close(drain=True)
    must sweep again after the join, or that caller hangs forever."""
    from bitcoinconsensus_tpu.serving.server import PendingVerify

    items = _items(2, bad_first=False)
    srv = serve(max_batch=2, flush_s=0.001, tenant_depth=8)

    # Simulate an unexpected worker death (anything escaping the burst
    # handler): settle what was popped — _run_burst's contract — then
    # propagate, killing the worker thread itself.
    def kill(first):
        for r in first:
            r._fail(RuntimeError("worker died"))
        raise RuntimeError("worker died")

    srv._run_burst = kill
    p0 = srv.submit(items[0])
    with pytest.raises(RuntimeError, match="worker died"):
        p0.result(timeout=30)
    srv._thread.join(30)  # the worker is now dead
    assert not srv._thread.is_alive()
    # Replay the race deterministically: a put that slipped in after the
    # dead worker's own drain (submit() already sheds by now, but the
    # queue itself is still open — exactly the raced window).
    stranded = PendingVerify(items[1], "default", 0.0)
    srv._queue.put(stranded)
    srv.close(drain=True)  # must NOT leave `stranded` unsettled
    with pytest.raises(OverloadError) as ei:
        stranded.result(timeout=5)
    assert ei.value.reason == SHED_CLOSED
    srv.close()  # and double-close stays a no-op
    assert srv.pending == 0


def test_double_close_concurrent_with_worker_crash(serve, monkeypatch):
    """Two concurrent close() calls racing a crashing worker: both must
    return (no deadlock, no exception), everything admitted settles."""
    import threading as _threading

    import bitcoinconsensus_tpu.serving.server as server_mod

    def boom(*a, **k):
        raise RuntimeError("driver crashed")
        yield  # pragma: no cover - makes this a generator function

    monkeypatch.setattr(server_mod, "verify_batch_stream", boom)
    items = _items(2, bad_first=False)
    srv = serve(max_batch=2, flush_s=0.001, tenant_depth=8)
    pend = [srv.submit(it) for it in items]
    errs = []

    def closer():
        try:
            srv.close(drain=True)
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    t1 = _threading.Thread(target=closer)
    t2 = _threading.Thread(target=closer)
    t1.start(); t2.start()
    t1.join(30); t2.join(30)
    assert not t1.is_alive() and not t2.is_alive()
    assert not errs
    for p in pend:
        with pytest.raises((RuntimeError, OverloadError)):
            p.result(timeout=5)  # settled explicitly, one way or the other
    assert srv.pending == 0


def test_pending_done_callback_runs_once_and_contains_errors():
    """add_done_callback: registered-then-settled and settled-then-
    registered both fire exactly once; a raising callback is contained
    (the settling thread survives)."""
    from bitcoinconsensus_tpu.models.batch import BatchResult
    from bitcoinconsensus_tpu.serving.server import PendingVerify

    req = PendingVerify("item", "t", 0.0)
    fired = []
    req.add_done_callback(lambda r: fired.append("pre"))

    def bad(_r):
        raise RuntimeError("broken observer")

    req.add_done_callback(bad)
    req._resolve(BatchResult.success())  # must not raise despite `bad`
    req._resolve(BatchResult.success())  # second settle: no-op, no refire
    assert fired == ["pre"]
    req.add_done_callback(lambda r: fired.append("post"))  # late: immediate
    assert fired == ["pre", "post"]
    assert req.result(timeout=1).ok
