"""Observability layer: registry semantics, span tracing, exposition,
the `Phases` thread-safety regression, and the no-sink overhead budget.

The telemetry contract (README "Observability"): instrumentation is on by
default, host-side only, and cheap enough that the no-sink fast path
costs < 1% of a small `verify_batch` — asserted here by event-cost
accounting rather than a flaky A/B wall-clock diff.
"""

import io
import json
import threading
import time

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.obs import (
    JsonlSink,
    MetricsRegistry,
    add_sink,
    get_registry,
    remove_sink,
    span,
)
from bitcoinconsensus_tpu.obs import metrics as M
from bitcoinconsensus_tpu.obs import spans as S
from bitcoinconsensus_tpu.obs.exposition import (
    diff_snapshots,
    snapshot_to_json,
    to_prometheus_text,
    validate_snapshot,
)
from bitcoinconsensus_tpu.utils.profiling import Phases


# ---------------------------------------------------------------------------
# Registry semantics.


def test_counter_labels_and_values():
    reg = MetricsRegistry()
    c = reg.counter("calls_total", "calls", ("entry",))
    c.inc(entry="verify")
    c.inc(3, entry="verify")
    c.inc(entry="batch")
    assert c.value(entry="verify") == 4
    assert c.value(entry="batch") == 1
    bound = c.labels(entry="verify")
    bound.inc(2)
    assert c.value(entry="verify") == 6
    with pytest.raises(ValueError):
        c.inc()  # missing label
    with pytest.raises(ValueError):
        c.inc(wrong="x")
    with pytest.raises(ValueError):
        c.inc(-1, entry="verify")  # counters only go up


def test_registry_get_or_create_and_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("shared_total", "h", ("code",))
    b = reg.counter("shared_total", "different help ok", ("code",))
    assert a is b  # same name+kind+labels -> shared instance
    with pytest.raises(ValueError):
        reg.gauge("shared_total")  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("shared_total", "h", ("other",))  # label conflict
    assert reg.names() == ["shared_total"]


def test_registry_reset_keeps_bound_handles():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "", ("k",))
    bound = c.labels(k="x")
    bound.inc(5)
    reg.reset()
    assert c.value(k="x") == 0
    bound.inc()  # bound handle survives the reset
    assert c.value(k="x") == 1


def test_histogram_bucket_edges():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "", buckets=(1, 2, 4))
    for v in (0.5, 1, 1.5, 2, 4, 5):
        h.observe(v)
    (s,) = h._samples()
    # Prometheus `le` semantics: a value equal to a boundary lands in
    # that bucket; cumulative counts; implicit +Inf catches the rest.
    assert s["buckets"] == [[1.0, 2], [2.0, 4], [4.0, 5], ["+Inf", 6]]
    assert s["count"] == 6
    assert s["sum"] == pytest.approx(14.0)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=())
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(2, 1))
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1, float("inf")))


def test_gauge_set_add():
    reg = MetricsRegistry()
    g = reg.gauge("fill", "", ("dev",))
    g.set(0.5, dev="0")
    g.add(0.25, dev="0")
    assert g.value(dev="0") == 0.75


# ---------------------------------------------------------------------------
# Spans.


class _ListSink:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)


def test_span_nesting_parent_ids_and_sink():
    sink = _ListSink()
    add_sink(sink)
    try:
        with span("outer", n=3) as outer:
            with span("inner") as inner:
                pass
        assert inner.parent_id == outer.span_id
        assert outer.parent_id is None
    finally:
        remove_sink(sink)
    # children exit (and are written) first
    assert [r["name"] for r in sink.records] == ["inner", "outer"]
    inner_rec, outer_rec = sink.records
    assert inner_rec["parent_id"] == outer_rec["span_id"]
    assert outer_rec["attrs"] == {"n": 3}
    assert outer_rec["dur_s"] >= inner_rec["dur_s"] >= 0


def test_span_exception_path():
    reg = get_registry()
    errs = reg.get("consensus_span_errors_total")
    before = errs.value(span="obs-test-boom")
    sink = _ListSink()
    add_sink(sink)
    try:
        with pytest.raises(RuntimeError):
            with span("obs-test-boom"):
                raise RuntimeError("boom")
    finally:
        remove_sink(sink)
    assert errs.value(span="obs-test-boom") == before + 1
    (rec,) = sink.records
    assert rec["error"] == "RuntimeError"


def test_span_aggregates_into_registry():
    reg = get_registry()
    hist = reg.get("consensus_span_duration_seconds")

    def count():
        for s in hist._samples():
            if s["labels"] == {"span": "obs-test-agg"}:
                return s["count"]
        return 0

    before = count()
    for _ in range(3):
        with span("obs-test-agg"):
            pass
    assert count() == before + 3


def test_broken_sink_never_breaks_a_span():
    """A dying sink must not take down a verify — and must not vanish
    silently either: every dropped record lands in
    `consensus_obs_sink_errors_total` (resilience triage contract)."""

    class Broken:
        def write(self, record):
            raise OSError("disk full")

    before = S._SINK_ERRORS.value(sink="Broken")
    b = Broken()
    add_sink(b)
    try:
        with span("obs-test-broken-sink"):
            pass  # must not raise
        with span("obs-test-broken-sink-2"):
            pass
    finally:
        remove_sink(b)
    assert S._SINK_ERRORS.value(sink="Broken") == before + 2


def test_jsonl_sink_roundtrip():
    buf = io.StringIO()
    sink = JsonlSink(buf)
    add_sink(sink)
    try:
        with span("obs-test-jsonl", kind="x"):
            pass
    finally:
        remove_sink(sink)
        sink.flush()
    lines = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert len(lines) == 1
    assert lines[0]["name"] == "obs-test-jsonl"
    assert lines[0]["attrs"] == {"kind": "x"}
    assert "thread" in lines[0] and "pid" in lines[0]


# ---------------------------------------------------------------------------
# Exposition.


def test_prometheus_golden_output():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", ("code",))
    c.inc(2, code="ok")
    c.inc(code='we"ird\nlabel\\x')
    reg.gauge("temp", "degrees").set(1.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1))
    h.observe(0.25)
    h.observe(0.5)
    assert to_prometheus_text(reg.snapshot()) == (
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 0\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 2\n'
        "lat_seconds_sum 0.75\n"
        "lat_seconds_count 2\n"
        "# HELP req_total requests\n"
        "# TYPE req_total counter\n"
        'req_total{code="ok"} 2\n'
        'req_total{code="we\\"ird\\nlabel\\\\x"} 1\n'
        "# HELP temp degrees\n"
        "# TYPE temp gauge\n"
        "temp 1.5\n"
    )


def test_validate_and_diff_snapshots():
    reg = MetricsRegistry()
    c = reg.counter("a_total", "", ("k",))
    c.inc(k="x")
    snap1 = reg.snapshot()
    assert validate_snapshot(snap1, ["a_total"]) == []
    assert validate_snapshot(snap1, ["missing_total"]) == [
        "required metric missing: missing_total"
    ]
    reg.gauge("g").set(float("nan"))
    assert any("non-finite" in p for p in validate_snapshot(reg.snapshot()))

    c.inc(2, k="x")
    c.inc(k="y")
    snap2 = reg.snapshot()
    del snap2["g"]
    lines = diff_snapshots(snap1, snap2)
    assert "  a_total{k=x} +2" in lines
    assert any("new sample" in line for line in lines)
    doc = json.loads(snapshot_to_json(snap1, workload="t"))
    assert doc["meta"] == {"workload": "t"}
    assert "a_total" in doc["metrics"]


# ---------------------------------------------------------------------------
# Phases: the thread-safety regression (bare-dict read-modify-write races)
# and adapter behavior.


def test_phases_threaded_hammer_exact_counts():
    ph = Phases()
    n_threads, iters = 8, 300
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for _ in range(iters):
            with ph("hammer"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = ph.report()
    # The racy dicts this regression-tests lost increments under exactly
    # this load; the locked adapter must be exact.
    assert rep["hammer"]["calls"] == n_threads * iters
    assert rep["hammer"]["secs"] >= 0
    assert ph.total() == pytest.approx(rep["hammer"]["secs"], abs=1e-6)
    ph.reset()
    assert ph.report() == {}


def test_phases_disabled_is_noop():
    ph = Phases(enabled=False)
    with ph("x"):
        pass
    assert ph.report() == {}


def test_phases_feed_registry_spans():
    reg = get_registry()
    hist = reg.get("consensus_span_duration_seconds")

    def count(name):
        for s in hist._samples():
            if s["labels"] == {"span": name}:
                return s["count"]
        return 0

    ph = Phases(scope="obstest")
    before = count("obstest.phase1")
    with ph("phase1"):
        pass
    assert count("obstest.phase1") == before + 1
    assert ph.report()["phase1"]["calls"] == 1


# `outer_secs`: the part of a phase's `secs` it spent as the outermost open
# phase of its `Phases` on its thread. Over a call made on one thread the
# `outer_secs` tile it, so wall - sum(outer_secs) is what no phase names.


def _nested(ph):
    with ph("dispatch"):
        with ph("shard_put"):
            with ph("inmost"):
                pass
        with ph("shard_exec"):
            pass
    return {"dispatch"}


def _siblings(ph):
    for name in ("parse", "accounting", "parse"):
        with ph(name):
            pass
    return {"parse", "accounting"}


def _same_name_nested(ph):
    with ph("sync"):
        with ph("sync"):
            time.sleep(0.002)  # so that the two levels differ after rounding
    return {"sync"}


def _other_instance_inside(ph):
    other = Phases(scope="obstest-other")
    with other("host"):  # another clock's phase does not make ours inner
        with ph("lone"):
            pass
    assert other.report()["host"]["outer_secs"] == other.report()["host"]["secs"]
    return {"lone"}


def _after_an_exception(ph):
    with pytest.raises(RuntimeError):
        with ph("outer"):
            with ph("raises"):
                raise RuntimeError("boom")
    with ph("next"):  # the depth came back down: outermost again
        pass
    return {"outer", "next"}


@pytest.mark.parametrize(
    "case", [_nested, _siblings, _same_name_nested, _other_instance_inside,
             _after_an_exception],
)
def test_phases_outer_secs_tile_a_call(case):
    ph = Phases(scope="obstest-outer")
    t0 = time.perf_counter()
    outermost = case(ph)
    wall = time.perf_counter() - t0
    rep = ph.report()
    for name, entry in rep.items():
        assert set(entry) == {"secs", "calls", "outer_secs"}
        if name in outermost and name != "sync":
            assert entry["outer_secs"] == entry["secs"]
        elif name not in outermost:
            assert entry["outer_secs"] == 0.0
        assert 0.0 <= entry["outer_secs"] <= entry["secs"]
    assert outermost <= set(rep)
    # rounding: six digits an entry
    assert sum(e["outer_secs"] for e in rep.values()) <= wall + 1e-6 * len(rep)
    # `secs` and `calls` are what they were: nested phases still overlap
    if case is _nested:
        assert {n: e["calls"] for n, e in rep.items()} == {
            "dispatch": 1, "shard_put": 1, "inmost": 1, "shard_exec": 1}
        assert rep["dispatch"]["secs"] >= rep["shard_put"]["secs"] >= rep["inmost"]["secs"]
        assert ph.total() == pytest.approx(sum(e["secs"] for e in rep.values()), abs=1e-5)
    if case is _same_name_nested:
        assert rep["sync"]["calls"] == 2 and 0 < rep["sync"]["outer_secs"] < rep["sync"]["secs"]
    ph.reset()
    assert ph.report() == {}


def test_phases_outermost_is_per_thread():
    """A phase open on another thread does not make this thread's phase
    inner: each thread's `outer_secs` tile that thread's own wall."""
    ph = Phases(scope="obstest-threads")
    inside = threading.Event()
    done = threading.Event()
    walls = {}

    def worker():
        t0 = time.perf_counter()
        with ph("worker"):
            inside.set()
            done.wait(30)
        walls["worker"] = time.perf_counter() - t0

    t = threading.Thread(target=worker)
    t0 = time.perf_counter()
    t.start()
    assert inside.wait(30)
    with ph("caller"):  # wholly inside the worker's phase, on this thread
        with ph("caller_inner"):
            pass
    walls["caller"] = time.perf_counter() - t0
    done.set()
    t.join()
    rep = ph.report()
    assert rep["worker"]["outer_secs"] == rep["worker"]["secs"] <= walls["worker"] + 1e-6
    assert rep["caller"]["outer_secs"] == rep["caller"]["secs"] <= walls["caller"] + 1e-6
    assert rep["caller_inner"]["outer_secs"] == 0.0 < rep["caller_inner"]["calls"]


# ---------------------------------------------------------------------------
# The set-up split: JAX's own monitoring events feed the registry.


def test_compile_listener_splits_a_first_call():
    """A function jitted for the first time is traced, lowered and compiled:
    each of the three stages rises, and the listeners are registered once
    however often `configure()` runs."""
    import jax
    import jax.numpy as jnp

    from bitcoinconsensus_tpu.utils import compile_cache

    compile_cache.configure()
    compile_cache.configure()
    seconds = get_registry().get("consensus_compile_seconds_total")
    assert get_registry().get("consensus_compile_cache_total") is not None
    x = jnp.arange(11, dtype=jnp.int32)
    x.block_until_ready()
    calls = []

    def witness(event, secs, **kw):
        calls.append(event)

    jax.monitoring.register_event_duration_secs_listener(witness)
    before = {s: seconds.value(stage=s) for s in ("trace", "lower", "backend")}
    try:
        fresh = jax.jit(lambda v: (v * 7 + 3) ^ 0x36)  # no other test's program
        assert np.asarray(fresh(x)).tolist()[:2] == [3 ^ 0x36, 10 ^ 0x36]
        rose = {s: seconds.value(stage=s) - before[s] for s in before}
        assert all(v > 0 for v in rose.values()), rose
        # one program lowered and compiled once (its ops are traced as
        # jaxprs of their own, so tracing reports more than once)
        assert calls.count("/jax/core/compile/jaxpr_trace_duration") >= 1
        for event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration"):
            assert calls.count(event) == 1, (event, calls)
        again = {s: seconds.value(stage=s) for s in before}
        assert np.asarray(fresh(x)).tolist()[2] == 17 ^ 0x36  # a warm call: no event
        assert {s: seconds.value(stage=s) for s in before} == again
    finally:
        jax.monitoring.unregister_event_duration_listener(witness)


def test_compile_stages_tile_a_nested_trace():
    """JAX reports a traced function's duration with, inside it, those of
    the jitted functions it calls and of the eager ops it compiles on the
    way. Each stage is credited its own time only, so the stages' sum stays
    under the wall time where the raw durations' sum does not have to."""
    import jax
    import jax.numpy as jnp

    from bitcoinconsensus_tpu.utils import compile_cache

    compile_cache.configure()
    seconds = get_registry().get("consensus_compile_seconds_total")
    stages = ("trace", "lower", "backend")
    raw = []

    def witness(event, secs, **kw):
        if event.startswith("/jax/core/compile/"):
            raw.append(secs)

    @jax.jit
    def inner(v):
        return v * 5 + 36

    @jax.jit
    def middle(v):
        return inner(v) ^ inner(v + 1)

    def outer(v):
        eager = (jnp.arange(3, dtype=jnp.int32) * 36).sum()  # compiles mid-trace
        return middle(v) + middle(v * 2) + eager

    x = jnp.arange(9, dtype=jnp.int32)
    x.block_until_ready()
    jax.monitoring.register_event_duration_secs_listener(witness)
    before = {s: seconds.value(stage=s) for s in stages}
    t0 = time.perf_counter()
    try:
        jax.jit(outer)(x).block_until_ready()
    finally:
        wall = time.perf_counter() - t0
        jax.monitoring.unregister_event_duration_listener(witness)
    rose = {s: seconds.value(stage=s) - before[s] for s in stages}
    assert all(v > 0 for v in rose.values()), rose
    assert len(raw) > 3  # nested reports there were
    assert sum(rose.values()) <= wall
    assert sum(rose.values()) < sum(raw)  # the nested ones were not counted twice


# ---------------------------------------------------------------------------
# No-sink overhead budget: event-cost accounting on a small verify_batch.


def _make_items(n):
    from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_LIBCONSENSUS
    from bitcoinconsensus_tpu.models.batch import BatchItem
    from test_batch import make_p2wpkh_spend

    items = []
    for i in range(n):
        txb, spk, amt = make_p2wpkh_spend(f"obs-ovh-{i}")
        items.append(
            BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                      spent_output_script=spk, amount=amt)
        )
    return items


@pytest.mark.usefixtures("warm_kernel")
def test_no_sink_overhead_under_one_percent(monkeypatch):
    """Telemetry left on by default must cost < 1% of a small
    verify_batch. Direct A/B wall-clock timing of so small a difference
    is noise; instead: count every telemetry event one call generates,
    microbenchmark each primitive, and bound events x cost against the
    measured call time."""
    from bitcoinconsensus_tpu.models.batch import verify_batch
    from bitcoinconsensus_tpu.models.sigcache import (
        ScriptExecutionCache,
        SigCache,
    )

    items = _make_items(8)

    def run():
        res = verify_batch(
            items,
            sig_cache=SigCache(cache_label="obs-ovh"),
            script_cache=ScriptExecutionCache(cache_label="obs-ovh-s"),
        )
        assert all(r.ok for r in res)

    run()  # warm the jit/compile caches; timing below excludes compiles

    # Pass 1: count telemetry events (class-level patches reach every
    # call site, including bound handles created at import time).
    events = {"counter": 0, "gauge": 0, "hist": 0}
    real_cinc, real_binc = M.Counter.inc, M._BoundCounter.inc
    real_gset, real_gadd = M.Gauge.set, M.Gauge.add
    real_bgset, real_bgadd = M._BoundGauge.set, M._BoundGauge.add
    real_obs = M.Histogram._observe

    def _count(kind, real):
        def wrapper(self, *a, **kw):
            events[kind] += 1
            return real(self, *a, **kw)
        return wrapper

    monkeypatch.setattr(M.Counter, "inc", _count("counter", real_cinc))
    monkeypatch.setattr(M._BoundCounter, "inc", _count("counter", real_binc))
    monkeypatch.setattr(M.Gauge, "set", _count("gauge", real_gset))
    monkeypatch.setattr(M.Gauge, "add", _count("gauge", real_gadd))
    monkeypatch.setattr(M._BoundGauge, "set", _count("gauge", real_bgset))
    monkeypatch.setattr(M._BoundGauge, "add", _count("gauge", real_bgadd))
    monkeypatch.setattr(M.Histogram, "_observe", _count("hist", real_obs))
    spans_before = next(S._ids)
    run()
    span_events = next(S._ids) - spans_before - 1
    monkeypatch.undo()

    # Pass 2: measure the call wall time without the counting overhead.
    wall = min(
        _timed(run) for _ in range(3)
    )

    # Microbenchmark each primitive on the real (global) registry types.
    reg = MetricsRegistry()
    c = reg.counter("ovh_total", "", ("k",)).labels(k="x")
    h = reg.histogram("ovh_hist")
    g = reg.gauge("ovh_gauge")
    n = 20_000
    cost_counter = _timed(lambda: [c.inc() for _ in range(n)]) / n
    cost_hist = _timed(lambda: [h.observe(0.1) for _ in range(n)]) / n
    cost_gauge = _timed(lambda: [g.set(1.0) for _ in range(n)]) / n

    def bench_span():
        for _ in range(n):
            with span("ovh-span"):
                pass

    # span cost includes its own histogram observe; subtract it so the
    # estimate below (which counts that observe under `hist`) doesn't
    # double-bill, flooring at the bare context-manager cost.
    cost_span = max(_timed(bench_span) / n - cost_hist, 0.0)

    estimated = (
        events["counter"] * cost_counter
        + events["gauge"] * cost_gauge
        + events["hist"] * cost_hist
        + span_events * cost_span
    )
    assert events["counter"] > 0 and events["hist"] > 0 and span_events > 0
    assert estimated < 0.01 * wall, (
        f"telemetry estimate {estimated * 1e6:.0f}us exceeds 1% of "
        f"verify_batch wall {wall * 1e3:.2f}ms "
        f"(events={events}, spans={span_events})"
    )


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Trace ids + cross-thread stitching (the serving submit->settle seam).


def test_root_span_defines_trace_children_inherit():
    sink = _ListSink()
    add_sink(sink)
    try:
        with span("trace-root") as root:
            assert root.trace == root.span_id
            assert S.current_trace() == root.trace
            assert S.current_span_id() == root.span_id
            with span("trace-child") as child:
                assert child.trace == root.trace
                assert child.trace != child.span_id
        assert S.current_trace() is None
        assert S.current_span_id() is None
    finally:
        remove_sink(sink)
    child_rec, root_rec = sink.records
    assert child_rec["trace"] == root_rec["trace"] == root_rec["span_id"]


def test_trace_context_stitches_across_threads():
    """A span opened on another thread inside `trace_context` must join
    the originating trace and parent to the handed-over span id — the
    submit->worker-settle seam, in miniature."""
    sink = _ListSink()
    add_sink(sink)
    handoff = {}
    try:
        with span("stitch-submit") as sub:
            handoff["trace"] = sub.trace
            handoff["parent"] = sub.span_id

        def worker():
            with S.trace_context(handoff["trace"], handoff["parent"]):
                with span("stitch-settle"):
                    pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    finally:
        remove_sink(sink)
    by_name = {r["name"]: r for r in sink.records}
    sub_rec = by_name["stitch-submit"]
    set_rec = by_name["stitch-settle"]
    assert set_rec["trace"] == sub_rec["trace"]
    assert set_rec["parent_id"] == sub_rec["span_id"]
    assert set_rec["thread"] != sub_rec["thread"]


def test_trace_context_nests_and_restores():
    with S.trace_context(777, 42):
        assert S.current_trace() == 777
        assert S.current_span_id() == 42
        with span("ctx-inner") as sp:
            assert sp.trace == 777
            assert sp.parent_id == 42
    assert S.current_trace() is None


# ---------------------------------------------------------------------------
# JsonlSink under perf-workload volume: bounded flush, idempotent close,
# write-after-close counted (never crashing the verify).


def test_jsonl_sink_bounded_flush():
    class FlushCountingIO(io.StringIO):
        def __init__(self):
            super().__init__()
            self.flushes = 0

        def flush(self):
            self.flushes += 1
            return super().flush()

    buf = FlushCountingIO()
    sink = JsonlSink(buf, flush_every=4)
    for i in range(10):
        sink.write({"i": i})
    # 10 records / flush_every=4 -> exactly 2 size-triggered flushes; at
    # most flush_every records are ever buffered.
    assert buf.flushes == 2
    sink.close()
    assert buf.flushes == 3  # close flushes the tail
    assert len(buf.getvalue().splitlines()) == 10


def test_jsonl_sink_close_idempotent_and_write_after_close_raises():
    buf = io.StringIO()
    sink = JsonlSink(buf)
    sink.write({"a": 1})
    sink.close()
    sink.close()  # idempotent
    sink.flush()  # no-op after close, must not raise
    with pytest.raises(ValueError):
        sink.write({"b": 2})
    assert len(buf.getvalue().splitlines()) == 1


def test_closed_jsonl_sink_counts_as_sink_error_not_crash():
    """A JsonlSink closed while still attached must not take down the
    spans riding it — the dropped records land in
    `consensus_obs_sink_errors_total{sink=JsonlSink}` for triage."""
    before = S._SINK_ERRORS.value(sink="JsonlSink")
    sink = JsonlSink(io.StringIO())
    add_sink(sink)
    try:
        sink.close()  # closed while attached (the late-removal bug)
        with span("obs-test-closed-sink"):
            pass  # must not raise
        with span("obs-test-closed-sink-2"):
            pass
    finally:
        remove_sink(sink)
    assert S._SINK_ERRORS.value(sink="JsonlSink") == before + 2
