"""Black-box flight recorder: ring bound + eviction, span subscription,
dump-on-trigger with redaction + metric deltas, the per-process dump
cap, and the disarmed-overhead budget.

The contract (README "Device profiling & flight recorder"): disarmed,
`record()` costs one global read; armed, the last CAPACITY events are
always available and any trigger produces a complete, redacted,
provenance-stamped dump.
"""

import json
import os
import time

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.obs import flight as F
from bitcoinconsensus_tpu.obs import get_registry, span


@pytest.fixture(autouse=True)
def _isolated_recorder():
    """Every test starts disarmed with an empty ring and a fresh dump
    budget, and cannot leak an armed recorder to the next test."""
    F.set_enabled(False)
    F.reset()
    yield
    F.set_enabled(False)
    F.reset()


def _events_count(kind):
    return get_registry().get(
        "consensus_flight_events_total").value(kind=kind)


def test_disarmed_record_is_noop():
    before = _events_count("noop-test")
    F.record("noop-test", detail="dropped")
    assert F.events() == []
    assert _events_count("noop-test") == before
    assert not F.enabled()


def test_ring_bound_and_eviction_order():
    F.set_enabled(True)
    extra = 50
    for i in range(F.CAPACITY + extra):
        F.record("tick", i=i)
    evs = F.events()
    assert len(evs) == F.CAPACITY  # bounded
    assert F.dropped() == extra
    # Oldest-first window: the first `extra` events were evicted.
    assert evs[0]["i"] == extra
    assert evs[-1]["i"] == F.CAPACITY + extra - 1
    assert all(a["t"] <= b["t"] for a, b in zip(evs, evs[1:]))


def test_armed_gauge_and_event_counter():
    snap = get_registry().snapshot()
    assert snap["consensus_flight_armed"]["samples"][0]["value"] == 0
    F.set_enabled(True)
    snap = get_registry().snapshot()
    assert snap["consensus_flight_armed"]["samples"][0]["value"] == 1
    before = _events_count("counted")
    F.record("counted")
    F.record("counted")
    assert _events_count("counted") == before + 2


def test_span_subscription_attaches_and_detaches():
    F.set_enabled(True)
    with span("flight.test.sub"):
        pass
    kinds = [(e["kind"], e.get("name")) for e in F.events()]
    assert ("span", "flight.test.sub") in kinds
    F.set_enabled(False)
    F.reset()
    with span("flight.test.after"):
        pass
    assert F.events() == []  # sink detached with the recorder


def test_trigger_dump_contents_and_redaction(tmp_path):
    F.set_enabled(True)
    F.record(
        "guard.anomaly", site="jax_backend.verdict", reason="checksum",
        pubkey=b"\x02" * 33, detail="mismatch",
    )
    F.record("ladder.demote", ladder="device", src="xla", dst="host")
    with span("flight.test.window"):
        pass
    path = F.trigger("quarantine", out_dir=str(tmp_path),
                     script_sig=b"\x51\x51", ladder="device")
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path).startswith("flight_dump_quarantine_")
    doc = json.loads(open(path).read())
    assert doc["schema"] == F.SCHEMA
    assert doc["trigger"] == "quarantine"
    # Provenance-stamped like every artifact in the repo.
    assert "platform" in doc["provenance"]
    # The whole window, oldest first.
    kinds = [e["kind"] for e in doc["events"]]
    assert kinds.index("guard.anomaly") < kinds.index("ladder.demote")
    assert "span" in kinds
    assert doc["events_dropped"] == 0
    # Redaction: sensitive keys never reach the dump in the clear.
    anomaly = doc["events"][kinds.index("guard.anomaly")]
    assert anomaly["pubkey"] == "<redacted:33>"
    assert anomaly["detail"] == "mismatch"  # innocuous fields survive
    assert doc["attrs"]["script_sig"] == "<redacted:2>"
    assert doc["attrs"]["ladder"] == "device"
    # Metric deltas since arming ride along for the post-mortem.
    assert isinstance(doc["metric_deltas"], list)
    # Dump counter lit.
    assert get_registry().get("consensus_flight_dumps_total").value(
        trigger="quarantine") >= 1


def test_redaction_recurses_and_handles_bytes():
    red = F._redact({
        "msg32": b"\x00" * 32,
        "nested": {"witness": ["a", "b"], "depth": 2},
        "blob": b"\x01\x02",
        "note": "fine",
    })
    assert red["msg32"] == "<redacted:32>"
    assert red["nested"]["witness"] == "<redacted:2>"
    assert red["nested"]["depth"] == 2
    assert red["blob"] == "<bytes:2>"  # unlabeled bytes still never leak
    assert red["note"] == "fine"


def test_trigger_disarmed_returns_none(tmp_path):
    assert F.trigger("cli", out_dir=str(tmp_path)) is None
    assert list(tmp_path.iterdir()) == []


def test_dump_cap_is_per_process(tmp_path, monkeypatch):
    F.set_enabled(True)
    monkeypatch.setattr(F, "MAX_DUMPS", 2)
    F.record("one")
    assert F.trigger("cap", out_dir=str(tmp_path)) is not None
    assert F.trigger("cap", out_dir=str(tmp_path)) is not None
    assert F.trigger("cap", out_dir=str(tmp_path)) is None  # cap hit
    F.reset()  # test-isolation helper restores the budget
    assert F.trigger("cap", out_dir=str(tmp_path)) is not None


def test_trigger_unwritable_dir_fails_closed():
    F.set_enabled(True)
    F.record("ev")
    assert F.trigger("cli", out_dir="/nonexistent/dir/path") is None


@pytest.mark.usefixtures("warm_kernel")
def test_disarmed_overhead_under_one_percent():
    """Event-cost accounting, mirroring the perf/obs budget tests: the
    disarmed `record()` hook priced by microbenchmark must cost < 1% of
    a small real verify for any plausible per-batch hook count."""
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
            sig_cache=SigCache(cache_label="flight-ovh"),
            script_cache=ScriptExecutionCache(cache_label="flight-ovh-s"),
        )
        assert all(r.ok for r in res)

    run()  # warm the jit/compile caches

    def _timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    assert not F.enabled()
    wall = min(_timed(run) for _ in range(3))
    reps = 100_000
    per_record = _timed(
        lambda: [F.record("x", a=1) for _ in range(reps)]
    ) / reps
    # Every resilience hook site fires at most a handful of records per
    # dispatch; 64 per batch is far beyond any real path.
    bound = 64 * per_record
    assert bound < 0.01 * wall, (
        f"disarmed record bound {bound * 1e6:.2f}us exceeds 1% of "
        f"verify_batch wall {wall * 1e3:.2f}ms"
    )


def test_resilience_sites_record_while_armed(tmp_path, monkeypatch):
    """The degradation ladder's demotion path records the transition
    into the ring BEFORE triggering, so a quarantine dump always holds
    its own cause (asserted end-to-end by consensus_chaos.py)."""
    from bitcoinconsensus_tpu.resilience.degrade import Ladder

    # Demotion fires a real quarantine trigger; keep its dump out of /tmp.
    monkeypatch.setenv("BITCOINCONSENSUS_TPU_FLIGHT_DIR", str(tmp_path))
    F.set_enabled(True)
    ladder = Ladder(("xla", "host"), "flight-test")
    for _ in range(ladder.demote_after):
        ladder.report("xla", ok=False)
    kinds = [e["kind"] for e in F.events()]
    assert "ladder.demote" in kinds
    ev = F.events()[kinds.index("ladder.demote")]
    assert ev["src"] == "xla" and ev["dst"] == "host"
    # ...and the paired trigger wrote exactly one quarantine dump there.
    dumps = list(tmp_path.glob("flight_dump_quarantine_*.json"))
    assert len(dumps) == 1
