"""Percentile and due-time arithmetic on hand-made samples."""

import math

import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [40.0, 10.0, 30.0, 20.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 40.0
    assert stats.percentile(xs, 50) == 25.0
    assert stats.median(xs) == 25.0
    assert stats.percentile(xs, 95) == pytest.approx(38.5)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_runs_from_due_time_and_failures_are_over_any_limit():
    due = [10.0, 10.5, 11.0, 11.5]
    done = [10.2, 10.9, None, 11.6]       # the third got no answer
    failed = [False, False, False, True]  # the fourth was answered wrongly
    lat = stats.request_latencies_ms(due, done, failed, fail_ms=13000.0)
    assert lat == pytest.approx([200.0, 400.0, 13000.0, 13000.0])
    # a stalled sender does not shorten it: latency never reads `sent`
    assert stats.percentile(lat, 95) > 10000.0


def test_stratified_gaps_span_exactly_n_over_rate_for_every_order():
    gaps = stats.stratified_gaps(500, 50.0)
    assert len(gaps) == 500 and min(gaps) > 0
    assert sum(gaps) == pytest.approx(10.0)
    # exponential shape: the median gap is ln 2 of the mean
    assert sorted(gaps)[250] == pytest.approx(math.log(2) / 50.0, rel=0.02)


def test_quota_is_exact_and_seedless():
    q = stats.quota(6000, {"p2wpkh": 0.55, "p2tr": 0.20, "p2pkh": 0.15, "p2wsh_multisig": 0.10})
    assert q == {"p2wpkh": 3300, "p2tr": 1200, "p2pkh": 900, "p2wsh_multisig": 600}
    assert sum(stats.quota(7, {"a": 1, "b": 1, "c": 1}).values()) == 7
