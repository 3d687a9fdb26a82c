"""The reduction from trace to numbers: on a hand-made trace, and on a
small trace recorded on a TPU v5e in PR 24 (data/tip-block.cold.trace.json,
the `load_xplane` form of a `--trace 1` run's slice, cut to a few connects)."""

import json
import os

import pytest

from benchmarks.harness import tracered

MS = 1_000_000


def _hand_made():
    ops = [("kernel", 10 * MS, 30 * MS), ("copy", 35 * MS, 10 * MS),  # overlap: union 10..45
           ("kernel", 60 * MS, 20 * MS), ("late", 95 * MS, 20 * MS)]  # clipped at 100
    mods = [("jit_verify_tiles(1)", 10 * MS, 35 * MS), ("jit_verify_tiles(1)", 60 * MS, 20 * MS),
            ("jit__verdict_checksum(2)", 80 * MS, 1 * MS)]
    host = {"main": [
        ("bench.trace_window", 0, 100 * MS),
        ("bench.reset", 0, 8 * MS),
        ("bench.connect", 8 * MS, 42 * MS),    # 8..50
        ("bench.reset", 50 * MS, 5 * MS),
        ("bench.connect", 55 * MS, 30 * MS),   # 55..85
        ("bench.connect", 90 * MS, 30 * MS),   # runs past the window: not counted
    ]}
    return {"device": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}, "host": host}


def test_union_merges_overlaps():
    assert tracered.union([(5, 9), (1, 3), (2, 4), (9, 12), (20, 20)]) == [(1, 4), (5, 12)]


def test_busy_idle_kernel_time_and_gaps_on_a_hand_made_trace():
    r = tracered.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.035 + 0.020 + 0.005)  # union, clipped to the window
    assert r["chips"] == 1
    assert dict(map(tuple, r["device_ops"]))["kernel"] == pytest.approx(0.050)
    assert r["modules"]["jit_verify_tiles(1)"] == pytest.approx(0.055)
    assert tracered.seconds_matching(r["modules"], r"verify_tiles|_verdict_checksum") == pytest.approx(0.056)
    gaps = dict(map(tuple, r["idle_gaps"]))
    # idle: 0..10 (mid 5: reset), 45..60 (mid 52.5: reset), 80..95 (mid 87.5: none)
    assert gaps["bench.reset"] == pytest.approx(0.025)
    assert gaps["outside any benchmark call"] == pytest.approx(0.015)
    inside = r["within"]["bench.connect"]
    assert inside["count"] == 2 and inside["span_s"] == pytest.approx(0.072)
    assert inside["busy_s"] == pytest.approx(0.035 + 0.020)
    assert tracered.seconds_matching(inside["modules"], "verify_tiles") == pytest.approx(0.055)


def test_two_chips_are_averaged():
    t = _hand_made()
    t["device"]["/device:TPU:1"] = {"XLA Ops": [("kernel", 0, 100 * MS)], "XLA Modules": []}
    r = tracered.reduce(t)
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx((0.060 + 0.100) / 2)


def test_a_trace_without_the_window_annotation_is_refused():
    t = _hand_made()
    t["host"]["main"] = t["host"]["main"][1:]
    with pytest.raises(ValueError):
        tracered.reduce(t)


RECORDED = os.path.join(os.path.dirname(__file__), "data", "tip-block.cold.trace.json")


def test_recorded_chip_trace_reduces_to_the_numbers_read_by_hand():
    with open(RECORDED) as f:
        doc = json.load(f)
    trace = {
        "device": {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                   for p, lines in doc["trace"]["device"].items()},
        "host": {ln: [tuple(e) for e in evs] for ln, evs in doc["trace"]["host"].items()},
    }
    r = tracered.reduce(trace)
    want = doc["read_by_hand"]
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    inside = r["within"]["bench.connect"]
    assert inside["count"] == want["connects"]
    from benchmarks.layers._trace import VERIFY_PROGRAMS
    kernel = tracered.seconds_matching(inside["modules"], VERIFY_PROGRAMS)
    assert kernel / inside["count"] * 1000 == pytest.approx(want["kernel_ms_per_connect"], rel=1e-6)
    assert inside["busy_s"] <= inside["span_s"]
