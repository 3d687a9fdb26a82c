"""`layers/_stages.py` on hand-made windows: the rise of one label pair of
the native stage clock's two families over the window's timed calls."""

import pytest

from benchmarks.layers import _stages


def ctx(kind, stages=None, fans=None, calls=4, n_blocks=5, grew=True):
    """A window of `calls` connects, or passes of `n_blocks` blocks, over
    which each (call, stage) of `stages` and (call, stat) of `fans` rose from
    2.0 by the seconds given; a family left None is not registered (the
    parent commit)."""
    def family(pairs, second, more):
        return {"samples": [{"labels": {"call": c, second: s}, "value": 2.0 + (v if more else 0.0)}
                            for (c, s), v in pairs.items()]}

    def snap(more):
        out = {"consensus_dispatch_total": {"samples": []}}
        if stages is not None:
            out[_stages.STAGES] = family(stages, "stage", more)
        if fans is not None:
            out[_stages.FAN_OUT] = family(fans, "stat", more)
        return out

    return {"cell": "made-up", "trace": None, "driver": {
        "kind": kind, "walls_s": [0.05] * calls, "pass_walls_s": [0.5] * calls,
        "n_blocks": n_blocks, "counters_before": snap(False), "counters_after": snap(grew)}}


STAGES = {("lanes", "order"): 0.008, ("lanes", "shards"): 0.1, ("accounting", "fill"): 0.02}
FANS = {("lanes", "held"): 0.8, ("lanes", "sum"): 0.2, ("digests", "held"): 0.2,
        ("digests", "sum"): 0.1, ("interpret", "held"): 4.0, ("interpret", "sum"): 3.0,
        ("lanes", "start_lag"): 0.004, ("digests", "start_lag"): 0.002,
        ("interpret", "start_lag"): 0.006}


def test_a_stage_is_its_pairs_rise_over_the_connects():
    assert _stages.stage_ms(ctx("connect", STAGES), "connect", "lanes", "order") == pytest.approx(2.0)
    assert _stages.stage_ms(ctx("connect", STAGES), "connect", "lanes", "shards") == pytest.approx(25.0)
    # a pair the window never raised reads 0, not None: the family is there
    assert _stages.stage_ms(ctx("connect", STAGES), "connect", "interpret", "merge") == 0.0


def test_a_streams_stage_is_over_blocks_times_passes():
    assert _stages.stage_ms(ctx("stream", STAGES), "stream", "accounting", "fill") == pytest.approx(1.0)
    assert _stages.stage_ms(ctx("stream", STAGES, calls=1, n_blocks=2), "stream",
                            "accounting", "fill") == pytest.approx(10.0)


def test_a_fan_out_stat_sums_the_calls_asked_for():
    c = ctx("connect", fans=FANS)
    assert _stages.fan_ms(c, "connect", _stages.SESSION_CALLS, "start_lag") == pytest.approx(3.0)
    assert _stages.fan_ms(c, "connect", ("lanes",), "start_lag") == pytest.approx(1.0)


def test_a_busy_share_is_sum_over_held_of_the_calls_asked_for():
    c = ctx("connect", fans=FANS)
    assert _stages.busy_share(c, "connect", ("lanes", "digests")) == pytest.approx(30.0)
    assert _stages.busy_share(c, "connect", ("interpret",)) == pytest.approx(75.0)


def test_nothing_to_read_is_none():
    # the parent commit: neither family registered
    assert _stages.stage_ms(ctx("connect"), "connect", "lanes", "order") is None
    assert _stages.fan_ms(ctx("connect"), "connect", ("lanes",), "start_lag") is None
    assert _stages.busy_share(ctx("connect"), "connect", ("lanes",)) is None
    # one family without the other
    assert _stages.busy_share(ctx("connect", STAGES), "connect", ("lanes",)) is None
    assert _stages.stage_ms(ctx("connect", fans=FANS), "connect", "lanes", "order") is None
    # no thread time held over the window
    assert _stages.busy_share(ctx("connect", fans=FANS, grew=False), "connect", ("lanes",)) is None
    # no timed call
    assert _stages.stage_ms(ctx("connect", STAGES, calls=0), "connect", "lanes", "order") is None
    assert _stages.busy_share(ctx("connect", fans=FANS, calls=0), "connect", ("lanes",)) is None
    # no snapshots
    bare = ctx("connect", STAGES, FANS)
    bare["driver"]["counters_after"] = None
    assert _stages.stage_ms(bare, "connect", "lanes", "order") is None


@pytest.mark.parametrize("cell,reader", [("stream", "connect"), ("connect", "stream"),
                                         ("serve", "connect"), ("reorg", "stream")])
def test_a_reader_of_one_kind_of_cell_is_none_in_another(cell, reader):
    c = ctx(cell, STAGES, FANS)
    assert _stages.stage_ms(c, reader, "accounting", "fill") is None
    assert _stages.fan_ms(c, reader, ("lanes",), "start_lag") is None
    assert _stages.busy_share(c, reader, ("lanes",)) is None
