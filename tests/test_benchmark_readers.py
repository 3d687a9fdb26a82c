"""The per-layer readers PRs 36 to 48 added, each on a made-up `ctx`.

`benchmarks/tests` is not part of tier-1, and a reader runs for real only
in a `--trace 1` run on the chip. Here every new reader gets a context
whose answer can be worked out by hand, and one of a program that lacks
what it reads (the parent commit, under this PR's benchmark files), where
it has to return None and not raise.
"""

import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SPAN_SECONDS = "consensus_span_duration_seconds"


def reader(metric: str):
    path = os.path.join(REPO, "benchmarks", "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmarks.layers.{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def ms(x):
    return pytest.approx(x, abs=1e-9)


# -- connect cells ---------------------------------------------------------


def sighash_ctx(kind, computed, reused, connects=3):
    """A window of `connects` connects of 6 inputs over which the labeled
    counter rose from (100, 1000) by (`computed`, `reused`)."""
    def snap(c, r):
        return {"consensus_sighash_total": {"samples": [
            {"labels": {"result": "computed"}, "value": c},
            {"labels": {"result": "reused"}, "value": r}]}}
    return {"cell": "made-up", "trace": None, "driver": {
        "kind": kind, "walls_s": [0.05] * connects, "n_inputs": 6,
        "counters_before": snap(100, 1000), "counters_after": snap(100 + computed, 1000 + reused)}}


@pytest.mark.parametrize("computed,want", [(36, 2.0), (414, 23.0), (0, 0.0)])
def test_sighashes_per_input_divides_computed_digests_by_inputs_verified(computed, want):
    # 18 inputs verified; the digests read again are not in it
    assert reader("sighashes_per_input.connect")(sighash_ctx("connect", computed, 378)) == ms(want)


@pytest.mark.parametrize("ctx", [
    {"cell": "made-up", "trace": None, "driver": {  # the parent: no such counter
        "kind": "connect", "walls_s": [0.05], "n_inputs": 6,
        "counters_before": {"consensus_dispatch_total": {"samples": []}},
        "counters_after": {"consensus_dispatch_total": {"samples": []}}}},
    sighash_ctx("connect", 36, 378, connects=0),  # a window that timed nothing
    sighash_ctx("stream", 36, 378),
    sighash_ctx("serve", 36, 378),
])
def test_sighashes_per_input_returns_none_with_nothing_to_read(ctx):
    assert reader("sighashes_per_input.connect")(ctx) is None


def phase(secs, outer=None, calls=1):
    return {"secs": secs, "calls": calls, "outer_secs": secs if outer is None else outer}


def connect_ctx(reports, walls):
    return {"cell": "made-up.connect", "trace": None, "driver": {
        "kind": "connect", "walls_s": walls, "phases": reports, "deltas": [],
        "counters_before": {}, "counters_after": {}, "n_inputs": 6}}


def _reports():
    """Three connects; `shard_put` runs inside `dispatch` (mesh), so its
    seconds are in `dispatch`'s and its `outer_secs` are 0."""
    out = []
    for k in range(3):
        out.append({
            "interpret": phase(0.010), "dispatch": phase(0.004), "shard_put": phase(0.001, 0.0),
            "sync": phase(0.020 + 0.001 * k), "sig_probe": phase(0.0005), "sig_insert": phase(0.0025),
            "block_free": phase(0.002), "release": phase(0.0001 * (k + 1)),
        })
    return out


WALLS = [0.0500, 0.0520, 0.0560]


def without(reports, *names, key=None):
    return [{n: ({k: v for k, v in e.items() if k != key} if key else e)
             for n, e in rep.items() if n not in names} for rep in reports]


def test_unphased_connect_is_wall_less_outer_secs():
    # sum of outer_secs: 0.0391, 0.0402, 0.0413 -> residues 10.9, 11.8, 14.7 ms
    assert reader("unphased_ms.connect")(connect_ctx(_reports(), WALLS)) == ms(11.8)


def test_sig_cache_connect_sums_probe_and_insert():
    assert reader("sig_cache_ms.connect")(connect_ctx(_reports(), WALLS)) == ms(3.0)
    cold = without(_reports(), "sig_probe")  # an empty cache is not probed
    assert reader("sig_cache_ms.connect")(connect_ctx(cold, WALLS)) == ms(2.5)


def test_teardown_connect_sums_block_free_and_release():
    assert reader("teardown_ms.connect")(connect_ctx(_reports(), WALLS)) == ms(2.2)


@pytest.mark.parametrize("metric,reports", [
    ("unphased_ms.connect", without(_reports(), key="outer_secs")),  # the parent's reports
    ("sig_cache_ms.connect", without(_reports(), "sig_probe", "sig_insert")),
    ("teardown_ms.connect", without(_reports(), "block_free")),  # `release` alone means less
    ("unphased_ms.connect", []), ("sig_cache_ms.connect", []), ("teardown_ms.connect", []),
])
def test_connect_readers_return_none_without_their_phases(metric, reports):
    assert reader(metric)(connect_ctx(reports, WALLS[: len(reports)])) is None


# -- the stream cell ---------------------------------------------------------


def stream_ctx(gaps, reports, n_blocks=3):
    return {"cell": "made-up.stream", "trace": None, "driver": {
        "kind": "stream", "pass_walls_s": [], "block_gaps_s": gaps, "first_result_s": [],
        "phases": reports, "counters_before": {}, "counters_after": {},
        "n_inputs": 6, "n_blocks": n_blocks, "depth": 2}}


def test_unphased_stream_skips_a_pass_first_record():
    # two passes of three blocks: a record a result, a gap between two
    first = {"parse": 9.0}  # the stretch before a pass's first result: no gap
    reports = [first, {"parse": 0.010, "sync": 0.002}, {"parse": 0.011, "sync": 0.001},
               first, {"parse": 0.010, "sync": 0.004}, {"parse": 0.012}]
    gaps = [0.0130, 0.0125, 0.0160, 0.0125]  # residues 1.0, 0.5, 2.0, 0.5 ms
    assert reader("unphased_ms.stream")(stream_ctx(gaps, reports)) == ms(0.75)


@pytest.mark.parametrize("gaps,reports,n_blocks", [
    ([0.01] * 4, [{"parse": 0.001}] * 5, 3),  # a record short
    ([0.01] * 5, [{"parse": 0.001}] * 6, 3),  # a gap too many
    ([], [{"parse": 0.001}] * 2, 1),  # one block a pass: no gap at all
    ([0.01], [], 2),
])
def test_unphased_stream_returns_none_when_the_lists_do_not_line_up(gaps, reports, n_blocks):
    assert reader("unphased_ms.stream")(stream_ctx(gaps, reports, n_blocks)) is None


# -- the served cell ---------------------------------------------------------


def hist(name, label, rows):
    return {name: {"samples": [
        {"labels": {label: value}, "sum": total, "count": count}
        for value, (total, count) in rows.items()]}}


def serve_ctx(before_spans, after_spans, batches=(10, 30), ingress=None):
    before = {**hist(SPAN_SECONDS, "span", before_spans),
              "consensus_serving_batches_total": {"samples": [{"labels": {}, "value": batches[0]}]}}
    after = {**hist(SPAN_SECONDS, "span", after_spans),
             "consensus_serving_batches_total": {"samples": [{"labels": {}, "value": batches[1]}]}}
    if ingress:
        before.update(hist("consensus_ingress_seconds", "stage", ingress[0]))
        after.update(hist("consensus_ingress_seconds", "stage", ingress[1]))
    return {"cell": "made-up.serve", "trace": None, "driver": {
        "kind": "serve", "latency_ms": [], "lag_ms": [], "requests": 0,
        "counters_before": before, "counters_after": after}}


BEFORE = {"serving.take": (1.0, 50), "batch.stream_begin": (2.0, 10),
          "batch.stream_finish": (1.0, 10), "verifier.sync": (0.5, 10), "serving.idle": (30.0, 5)}
AFTER = {"serving.take": (1.1, 70), "batch.stream_begin": (2.16, 30),
         "batch.stream_finish": (1.1, 30), "verifier.sync": (0.56, 30), "serving.idle": (37.0, 9)}


@pytest.mark.parametrize("metric,want", [
    ("take_wait_ms.serve", 5.0),  # 0.1 s over 20 batches
    ("host_ms.serve", 10.0),  # (0.16 + 0.1 - 0.06) s over 20 batches
    ("settle_wait_ms.serve", 3.0),
])
def test_serve_span_readers_divide_by_batches(metric, want):
    assert reader(metric)(serve_ctx(BEFORE, AFTER)) == ms(want)


def test_a_span_first_seen_inside_the_window_counts_from_zero():
    before = {k: v for k, v in BEFORE.items() if k != "serving.take"}
    assert reader("take_wait_ms.serve")(serve_ctx(before, AFTER)) == ms(55.0)


@pytest.mark.parametrize("metric,gone", [
    ("take_wait_ms.serve", "serving.take"),  # the parent has no such span
    ("host_ms.serve", "batch.stream_begin"),
    ("settle_wait_ms.serve", "verifier.sync"),
])
def test_serve_span_readers_return_none_without_their_span(metric, gone):
    after = {k: v for k, v in AFTER.items() if k != gone}
    assert reader(metric)(serve_ctx(BEFORE, after)) is None
    assert reader(metric)(serve_ctx(BEFORE, AFTER, batches=(10, 10))) is None  # no batch


def test_ingress_reader_adds_the_two_stage_means():
    ingress = ({"decode": (0.010, 100), "respond": (0.050, 100)},
               {"decode": (0.030, 300), "respond": (0.250, 300)})
    # means: decode 0.1 ms, respond 1.0 ms
    assert reader("ingress_ms.serve")(serve_ctx(BEFORE, AFTER, ingress=ingress)) == ms(1.1)


@pytest.mark.parametrize("ingress", [
    None,  # the parent: no such histogram
    ({"decode": (0.01, 100)}, {"decode": (0.03, 300)}),  # a stage missing
    ({"decode": (0.01, 100), "respond": (0.05, 100)},) * 2,  # no request in the window
])
def test_ingress_reader_returns_none_without_both_stages(ingress):
    assert reader("ingress_ms.serve")(serve_ctx(BEFORE, AFTER, ingress=ingress)) is None


# -- set-up, every cell ------------------------------------------------------


def setup_ctx(kind, stages):
    before = {}
    if stages is not None:
        before["consensus_compile_seconds_total"] = {"samples": [
            {"labels": {"stage": s}, "value": v} for s, v in stages.items()]}
    return {"cell": "made-up", "trace": None,
            "driver": {"kind": kind, "counters_before": before, "counters_after": {}}}


STAGES = {"trace": 40.5, "lower": 4.25, "backend": 50.0, "cache_load": 1.5}


@pytest.mark.parametrize("kind", ["connect", "stream", "serve"])
@pytest.mark.parametrize("metric,want", [("trace_lower_s.setup", 44.75), ("compile_s.setup", 50.0)])
def test_setup_readers_read_the_window_opening_snapshot(kind, metric, want):
    assert reader(metric)(setup_ctx(kind, STAGES)) == ms(want)


@pytest.mark.parametrize("metric", ["trace_lower_s.setup", "compile_s.setup"])
def test_setup_readers_return_none_on_a_program_without_the_counter(metric):
    assert reader(metric)(setup_ctx("connect", None)) is None
    # registered and never raised (a process that compiled nothing) reads 0
    assert reader(metric)(setup_ctx("connect", {})) == 0.0


@pytest.mark.parametrize("metric,ctx", [
    ("unphased_ms.connect", stream_ctx([0.01], [{"parse": 0.001}] * 2, 2)),
    ("sig_cache_ms.connect", serve_ctx(BEFORE, AFTER)),
    ("teardown_ms.connect", serve_ctx(BEFORE, AFTER)),
    ("unphased_ms.stream", connect_ctx(_reports(), WALLS)),
    ("take_wait_ms.serve", connect_ctx(_reports(), WALLS)),
    ("host_ms.serve", stream_ctx([0.01], [{"parse": 0.001}] * 2, 2)),
    ("settle_wait_ms.serve", connect_ctx(_reports(), WALLS)),
    ("ingress_ms.serve", connect_ctx(_reports(), WALLS)),
])
def test_a_reader_in_another_kind_of_cell_returns_none(metric, ctx):
    assert reader(metric)(ctx) is None


# -- the coin tables' probes (PR 40) -----------------------------------------


def probes_ctx(kind, view, block, calls=3, n_blocks=None):
    """A window of `calls` timed calls (connects, or passes of `n_blocks`
    blocks) of 6-input blocks over which the counter rose from (500, 70) by
    (`view`, `block`)."""
    def snap(v, b):
        return {"consensus_coin_probes_total": {"samples": [
            {"labels": {"table": "view"}, "value": v},
            {"labels": {"table": "block"}, "value": b}]}}
    walls = "walls_s" if kind == "connect" else "pass_walls_s"
    d = {"kind": kind, walls: [0.05] * calls, "n_inputs": 6,
         "counters_before": snap(500, 70), "counters_after": snap(500 + view, 70 + block)}
    if n_blocks is not None:
        d["n_blocks"] = n_blocks
    return {"cell": "made-up", "trace": None, "driver": d}


@pytest.mark.parametrize("view,block,want", [(54, 27, 4.5), (72, 27, 5.5), (0, 0, 0.0)])
def test_coin_probes_per_input_connect_sums_both_tables_over_inputs_connected(view, block, want):
    # 3 connects x 6 inputs; 3 outputs a block: (3 x 6 + 3 x 3) / 6 = 4.5
    assert reader("coin_probes_per_input.connect")(probes_ctx("connect", view, block)) == ms(want)


def test_coin_probes_per_input_stream_divides_by_the_blocks_of_every_pass():
    # 2 passes x 4 blocks x 6 inputs = 48 inputs connected
    ctx = probes_ctx("stream", 160, 80, calls=2, n_blocks=4)
    assert reader("coin_probes_per_input.stream")(ctx) == ms(5.0)


_NO_COUNTER = {"consensus_dispatch_total": {"samples": []}}


@pytest.mark.parametrize("metric,ctx", [
    # the parent: no such counter, in a cell of the reader's own kind
    ("coin_probes_per_input.connect", {"cell": "made-up", "trace": None, "driver": {
        "kind": "connect", "walls_s": [0.05], "n_inputs": 6,
        "counters_before": _NO_COUNTER, "counters_after": _NO_COUNTER}}),
    ("coin_probes_per_input.stream", {"cell": "made-up", "trace": None, "driver": {
        "kind": "stream", "pass_walls_s": [0.05], "n_inputs": 6, "n_blocks": 4,
        "counters_before": _NO_COUNTER, "counters_after": _NO_COUNTER}}),
    # a window that timed nothing
    ("coin_probes_per_input.connect", probes_ctx("connect", 54, 27, calls=0)),
    ("coin_probes_per_input.stream", probes_ctx("stream", 54, 27, calls=0, n_blocks=4)),
    # another kind of cell
    ("coin_probes_per_input.connect", probes_ctx("stream", 54, 27, n_blocks=4)),
    ("coin_probes_per_input.connect", probes_ctx("serve", 54, 27)),
    ("coin_probes_per_input.stream", probes_ctx("connect", 54, 27)),
])
def test_coin_probes_per_input_returns_none_with_nothing_to_read(metric, ctx):
    assert reader(metric)(ctx) is None


# -- the pieces a dispatch travels in (PR 43) ----------------------------------


def transfers_ctx(kind, pieces_in, pieces_out, dispatches, counter=True):
    """A window over which `dispatches` dispatches went out (some on each
    backend) and the transfers counter rose by (`pieces_in`, `pieces_out`)
    from (40, 40); `counter` False is a program without the counter."""
    def snap(i, o, d):
        out = {"consensus_dispatch_total": {"samples": [
            {"labels": {"backend": "pallas"}, "value": 20 + d},
            {"labels": {"backend": "xla"}, "value": 3}]}}
        if counter:
            out["consensus_dispatch_transfers_total"] = {"samples": [
                {"labels": {"dir": "in"}, "value": i}, {"labels": {"dir": "out"}, "value": o}]}
        return out
    return {"cell": "made-up", "trace": None, "driver": {
        "kind": kind, "counters_before": snap(40, 40, 0),
        "counters_after": snap(40 + pieces_in, 40 + pieces_out, dispatches)}}


@pytest.mark.parametrize("metric,kind", [("transfers_per_dispatch.connect", "connect"),
                                         ("transfers_per_dispatch.stream", "stream")])
@pytest.mark.parametrize("pieces_in,pieces_out,dispatches,want", [
    (10, 10, 10, 2.0),   # packed: one put, one host copy asked for
    (70, 40, 10, 11.0),  # the seven-argument launch, its checksum program, four pulls
    (35, 35, 35, 2.0),
])
def test_transfers_per_dispatch_is_pieces_both_ways_over_dispatches(
        metric, kind, pieces_in, pieces_out, dispatches, want):
    assert reader(metric)(transfers_ctx(kind, pieces_in, pieces_out, dispatches)) == ms(want)


@pytest.mark.parametrize("metric,ctx", [
    # the parent: no such counter, in a cell of the reader's own kind
    ("transfers_per_dispatch.connect", transfers_ctx("connect", 0, 0, 10, counter=False)),
    ("transfers_per_dispatch.stream", transfers_ctx("stream", 0, 0, 10, counter=False)),
    # a window that dispatched nothing
    ("transfers_per_dispatch.connect", transfers_ctx("connect", 0, 0, 0)),
    ("transfers_per_dispatch.stream", transfers_ctx("stream", 0, 0, 0)),
    # another kind of cell, a window without snapshots
    ("transfers_per_dispatch.connect", transfers_ctx("stream", 10, 10, 10)),
    ("transfers_per_dispatch.connect", transfers_ctx("serve", 10, 10, 10)),
    ("transfers_per_dispatch.stream", transfers_ctx("connect", 10, 10, 10)),
    ("transfers_per_dispatch.stream", {"cell": "made-up", "trace": None,
                                       "driver": {"kind": "stream"}}),
])
def test_transfers_per_dispatch_returns_none_with_nothing_to_read(metric, ctx):
    assert reader(metric)(ctx) is None


# -- the walk's share of the pre-recorded pairings (PR 41) ---------------------

_WALK = "consensus_multisig_walk_pairings_total"
_SPEC = "consensus_multisig_spec_pairings_total"


def walk_ctx(kind, walk, spec, connects=3, names=(_WALK, _SPEC)):
    """A window of `connects` connects over which the two counters rose
    from (700, 1100) by (`walk`, `spec`); `names`: what the program has."""
    def snap(w, s):
        both = {_WALK: w, _SPEC: s}
        return {n: {"samples": [{"labels": {}, "value": both[n]}]} for n in names}
    return {"cell": "made-up", "trace": None, "driver": {
        "kind": kind, "walls_s": [0.8] * connects, "n_inputs": 5,
        "counters_before": snap(700, 1100), "counters_after": snap(700 + walk, 1100 + spec)}}


@pytest.mark.parametrize("walk,spec,want", [
    (300, 1560, 100 * 20 / 104),  # 8-of-20 by the eight first-pushed keys: 20 of 104 an input
    (300, 300, 100.0),            # 1-of-20 by the key tried last: every lane is the walk's
    (0, 12, 0.0),
])
def test_walk_share_of_spec_divides_the_walk_by_the_band(walk, spec, want):
    assert reader("walk_share_of_spec.connect")(walk_ctx("connect", walk, spec)) == ms(want)


@pytest.mark.parametrize("ctx", [
    walk_ctx("connect", 0, 1560, names=(_SPEC,)),  # the parent: no walk counter
    walk_ctx("connect", 300, 0),                   # a window that pre-recorded nothing
    walk_ctx("stream", 300, 1560),                 # another kind of cell
    walk_ctx("serve", 300, 1560),
    {"cell": "made-up", "trace": None, "driver": {"kind": "connect", "walls_s": [0.8], "n_inputs": 5}},
])
def test_walk_share_of_spec_returns_none_with_nothing_to_read(ctx):
    assert reader("walk_share_of_spec.connect")(ctx) is None

# -- the bytes behind the ECDSA digests, and the rate a thread hashes them at (PR 45) ------

_SH_BYTES = "consensus_sighash_bytes_total"
_SH_SECONDS = "consensus_sighash_seconds_total"


def sighash_work_ctx(kind, legacy, bip143, thread_s, connects=3, inputs=5, names=(_SH_BYTES, _SH_SECONDS)):
    """A window of `connects` connects of `inputs` inputs over which the two
    labeled counters rose from made-up levels by (`legacy`, `bip143`) bytes
    and `thread_s` seconds, split between the kinds; `names`: what the
    program has."""
    def snap(leg, seg, secs):
        both = {_SH_BYTES: [("legacy", leg), ("bip143", seg)],
                _SH_SECONDS: [("legacy", secs * 0.75), ("bip143", secs * 0.25)]}
        return {n: {"samples": [{"labels": {"kind": k}, "value": v} for k, v in both[n]]}
                for n in names}
    return {"cell": "made-up", "trace": None, "driver": {
        "kind": kind, "walls_s": [0.3] * connects, "n_inputs": inputs,
        "counters_before": snap(9_000, 4_000, 2.0),
        "counters_after": snap(9_000 + legacy, 4_000 + bip143, 2.0 + thread_s)}}


@pytest.mark.parametrize("legacy,bip143,want", [
    (15 * 228_404, 0, 228.404),   # the megatransaction: the whole transaction an input
    (15 * 300, 15 * 182, 0.482),  # a block of small transactions, both kinds summed
    (0, 0, 0.0),
])
def test_sighash_kb_per_input_divides_the_bytes_by_inputs_verified(legacy, bip143, want):
    ctx = sighash_work_ctx("connect", legacy, bip143, 0.5)
    assert reader("sighash_kb_per_input.connect")(ctx) == ms(want)


@pytest.mark.parametrize("legacy,bip143,thread_s,want", [
    (3_000_000_000, 0, 6.0, 500.0),     # 3 GB in six thread seconds
    (1_000_000, 500_000, 0.003, 500.0),  # both kinds, bytes and seconds alike
])
def test_sighash_mb_per_s_divides_the_bytes_by_the_thread_seconds(legacy, bip143, thread_s, want):
    ctx = sighash_work_ctx("connect", legacy, bip143, thread_s)
    assert reader("sighash_mb_per_s.connect")(ctx) == ms(want)


@pytest.mark.parametrize("metric,ctx", [
    # the parent: neither counter; a program with the bytes and no clock
    ("sighash_kb_per_input.connect", sighash_work_ctx("connect", 900, 0, 0.5, names=())),
    ("sighash_mb_per_s.connect", sighash_work_ctx("connect", 900, 0, 0.5, names=())),
    ("sighash_mb_per_s.connect", sighash_work_ctx("connect", 900, 0, 0.5, names=(_SH_BYTES,))),
    # a window that spent no time hashing, one that timed no connect
    ("sighash_mb_per_s.connect", sighash_work_ctx("connect", 0, 0, 0.0)),
    ("sighash_kb_per_input.connect", sighash_work_ctx("connect", 900, 0, 0.5, connects=0)),
    # another kind of cell, a window without snapshots
    ("sighash_kb_per_input.connect", sighash_work_ctx("stream", 900, 0, 0.5)),
    ("sighash_mb_per_s.connect", sighash_work_ctx("serve", 900, 0, 0.5)),
    ("sighash_kb_per_input.connect", {"cell": "made-up", "trace": None,
                                      "driver": {"kind": "connect", "walls_s": [0.3], "n_inputs": 5}}),
    ("sighash_mb_per_s.connect", {"cell": "made-up", "trace": None,
                                  "driver": {"kind": "connect", "walls_s": [0.3], "n_inputs": 5}}),
])
def test_sighash_work_readers_return_none_with_nothing_to_read(metric, ctx):
    assert reader(metric)(ctx) is None


_TILES = "consensus_dispatch_tiles_total"


def tile_ctx(window, deltas, kind="connect", has_counter=True):
    """A window over which the registry rose by `window` = (full steps,
    half steps, dispatches, padded lanes) from made-up levels, whose timed
    connects dispatched `deltas` = [(dispatches, padded lanes), ...]."""
    def snap(full, half, dispatches, padded):
        out = {"consensus_dispatch_total": {"samples": [
                   {"labels": {"backend": "pallas"}, "value": dispatches}]},
               "consensus_dispatch_padded_lanes_total": {"samples": [
                   {"labels": {}, "value": padded}]}}
        if has_counter:
            out[_TILES] = {"samples": [{"labels": {"rows": "8"}, "value": full},
                                       {"labels": {"rows": "4"}, "value": half}]}
        return out
    base = (40, 7, 12, 44544)
    return {"cell": "made-up", "trace": None, "driver": {
        "kind": kind, "walls_s": [0.05] * len(deltas), "n_inputs": 6,
        "deltas": [{"consensus_dispatch_total": d, "consensus_dispatch_padded_lanes_total": p}
                   for d, p in deltas],
        "counters_before": snap(*base),
        "counters_after": snap(*(b + w for b, w in zip(base, window)))}}


@pytest.mark.parametrize("window,deltas,want", [
    # three connects of ten 8,192-lane chunks: 240 dense steps, nothing else
    ((240, 0, 30, 245760), [(10, 81920)] * 3, 100.0),
    # the four-chip cell: a dispatch is four shards of two dense steps
    ((16, 0, 2, 16384), [(1, 8192)] * 2, 100.0),
    # the warm cell: an untimed 8,192-lane precharge and a timed 512-lane
    # connect an iteration; the connects' steps are the half-filled ones
    ((24, 3, 6, 26112), [(1, 512)] * 3, 0.0),
    # a connect of one chunk and a 512-lane remainder: 8 dense steps of 9
    ((16, 2, 4, 17408), [(2, 8704)] * 2, 100 * 8 / 9),
])
def test_full_tile_share_is_the_dense_steps_of_the_timed_connects(window, deltas, want):
    assert reader("full_tile_share.connect")(tile_ctx(window, deltas)) == ms(want)


@pytest.mark.parametrize("ctx", [
    tile_ctx((0, 0, 3, 24576), [(1, 8192)] * 3, has_counter=False),  # the parent: no such counter
    tile_ctx((0, 0, 3, 24), [(1, 8)] * 3),           # XLA rungs only: no Pallas step ran
    tile_ctx((24, 3, 6, 26112), []),                 # a window that timed nothing
    # untimed dispatches of shapes that leave two splits open
    tile_ctx((9, 2, 4, 10240), [(2, 5120)]),
    tile_ctx((240, 0, 30, 245760), [(10, 81920)] * 3, kind="stream"),
    tile_ctx((240, 0, 30, 245760), [(10, 81920)] * 3, kind="serve"),
    {"cell": "made-up", "trace": None, "driver": {"kind": "connect", "walls_s": [0.8], "n_inputs": 5}},
])
def test_full_tile_share_returns_none_with_nothing_to_read(ctx):
    assert reader("full_tile_share.connect")(ctx) is None


# -- the reorganisation cell (PR 48) -------------------------------------------

REORG = ("disconnect_ms.reorg", "undo_probes_per_input.reorg", "cache_hit_share.reorg",
         "warm_result_gap_ms.reorg", "fresh_result_gap_ms.reorg", "settle_wait_ms.reorg",
         "kernel_ms.reorg", "device_idle.reorg",
         # the stream's layers, read over the timed reorganisations
         "host_ms.reorg", "coin_probes_per_input.reorg", "unphased_ms.reorg",
         "overlap_share.reorg", "transfers_per_dispatch.reorg")


def reorg_ctx(turns=3, trace=True, **over):
    """A window of `turns` sound reorganisations: two disconnects each
    (8 and 10 ms, a millisecond more a turn), gaps first call -> B1 -> B2 ->
    B3 of 30, 20 and 40 ms (two more a turn), `sync` 4 ms and one more a
    turn, `undo` 3 ms and `accounting` 80 of which 10 lie inside another
    phase; the probes, hits, lookups, dispatches and pieces a
    reorganisation's calls made; and a traced slice that holds two of them."""
    driver = {
        "kind": "reorg", "walls_s": [0.1 + 0.01 * k for k in range(turns)],
        "disconnect_s": [x + 0.001 * k for k in range(turns) for x in (0.008, 0.010)],
        "gaps_s": [[g + 0.002 * k for g in (0.030, 0.020, 0.040)] for k in range(turns)],
        "phases": [{"sync": phase(0.004 + 0.001 * k), "undo": phase(0.003),
                    "accounting": phase(0.080, outer=0.070)} for k in range(turns)],
        "deltas": [{"undo_probes": 17104.0, "connect_probes": 76518.0,
                    "consensus_cache_hits_total": 12000.0,
                    "consensus_cache_lookups_total": 18000.0,
                    "consensus_dispatch_total": 3.0,
                    "consensus_dispatch_transfers_total": 6.0} for _ in range(turns)],
        "disconnected_inputs": 12000, "n_inputs": 6000, "verdicts": 18000,
        "counters_before": {}, "counters_after": {}, **over}
    within = {"bench.reorg": {"count": 2, "span_s": 0.25, "busy_s": 0.02, "modules": {
        "jit_packed_verify_tiles(3)": 0.016, "jit_something_else": 0.5}}}
    return {"cell": "made-up.reorg", "driver": driver, "trace": {
        "busy_s": 1.0, "window_s": 4.0, "within": within} if trace else None}


@pytest.mark.parametrize("metric,want", [
    ("disconnect_ms.reorg", 10.0),          # median of 8, 10, 9, 11, 10, 12
    ("undo_probes_per_input.reorg", 17104 / 12000),
    ("cache_hit_share.reorg", 100.0 * 12 / 18),
    ("warm_result_gap_ms.reorg", 27.0),     # median of 30, 20, 32, 22, 34, 24
    ("fresh_result_gap_ms.reorg", 42.0),
    ("host_ms.reorg", 83.0),                # every phase but `sync`
    ("coin_probes_per_input.reorg", 76518 / 18000),  # (3 x 18,000 + 3 x 7,506) / 18,000
    ("unphased_ms.reorg", 110.0 - 5.0 - 3.0 - 70.0),  # the middle turn: wall less the outer seconds
    ("overlap_share.reorg", 100.0 * (1 - 5.0 / 8.0)),  # mean `sync` 5 ms under 8 ms of kernel
    ("transfers_per_dispatch.reorg", 2.0),
    ("settle_wait_ms.reorg", 5.0),
    ("kernel_ms.reorg", 8.0),               # 16 ms of the verify program in two calls
    ("device_idle.reorg", 92.0),
])
def test_reorg_readers_read_the_timed_reorganisations(metric, want):
    assert reader(metric)(reorg_ctx()) == ms(want)


@pytest.mark.parametrize("metric", REORG)
@pytest.mark.parametrize("ctx", [
    reorg_ctx(turns=0),                                        # a window that timed none
    connect_ctx(_reports(), WALLS),                            # another kind of cell
    {"cell": "made-up", "trace": None, "driver": {"kind": "stream"}},
], ids=["no-turn", "connect", "stream"])
def test_reorg_readers_return_none_with_nothing_to_read(metric, ctx):
    assert reader(metric)(ctx) is None


@pytest.mark.parametrize("metric,ctx", [
    ("kernel_ms.reorg", reorg_ctx(trace=False)),
    ("device_idle.reorg", reorg_ctx(trace=False)),
    # a program that counts no `undo` probes, and one whose reports lack `sync`
    ("undo_probes_per_input.reorg", reorg_ctx(deltas=[{"consensus_cache_hits_total": 1.0}])),
    ("cache_hit_share.reorg", reorg_ctx(deltas=[{"undo_probes": 1.0}])),
    ("cache_hit_share.reorg", reorg_ctx(deltas=[
        {"consensus_cache_hits_total": 0.0, "consensus_cache_lookups_total": 0.0}])),
    ("settle_wait_ms.reorg", reorg_ctx(phases=[{"undo": phase(0.003)}])),
    ("overlap_share.reorg", reorg_ctx(trace=False)),
    ("overlap_share.reorg", reorg_ctx(phases=[{"undo": phase(0.003)}] * 3)),
    # a program whose tables are not told apart, whose launch counts no piece,
    # and a driver that kept `secs` alone
    ("coin_probes_per_input.reorg", reorg_ctx(deltas=[{"undo_probes": 1.0}])),
    ("transfers_per_dispatch.reorg", reorg_ctx(deltas=[{"consensus_dispatch_total": 3.0}])),
    ("transfers_per_dispatch.reorg", reorg_ctx(deltas=[
        {"consensus_dispatch_total": 0.0, "consensus_dispatch_transfers_total": 0.0}])),
    ("unphased_ms.reorg", reorg_ctx(phases=[{"undo": {"secs": 0.003}}] * 3)),
    ("unphased_ms.reorg", reorg_ctx(phases=[{"undo": phase(0.003)}])),  # three walls, one report
])
def test_a_reorg_reader_returns_none_without_its_source(metric, ctx):
    assert reader(metric)(ctx) is None


# -- the native stage clock (PR 50) ----------------------------------------

_STAGE_SECONDS = "consensus_native_stage_seconds_total"
_FAN_SECONDS = "consensus_fan_out_seconds_total"
STAGE_CELLS = ["tip-block.cold", "worst-block.sigops", "worst-block-mesh4.sigops",
               "taproot-block.cold", "worst-block-multisig20.fanout",
               "worst-block-quadratic.sighash"]
ACCT_STAGES = ("decide", "fill", "copy")
# reader -> what it reads: the (call, stage) pair of the stage family, or the
# calls whose `stat` it sums of the fan-out family ("share": sum over held)
SESSION_STAGE_READERS = {
    "prep_order_ms.connect": ("lanes", "order"),
    "prep_shards_ms.connect": ("lanes", "shards"),
    "prep_digests_ms.connect": ("digests", "shards"),
    "prep_busy_share.connect": (("lanes", "digests"), "share"),
    "interp_workers_ms.connect": ("interpret", "workers"),
    "interp_merge_ms.connect": ("interpret", "merge"),
    "interp_busy_share.connect": (("interpret",), "share"),
    "fan_start_lag_ms.connect": (("interpret", "lanes", "digests"), "start_lag"),
}
STAGE_READERS = {
    **{name: ("connect", reads) for name, reads in SESSION_STAGE_READERS.items()},
    **{f"acct_{stage}_ms.connect": ("connect", ("accounting", stage)) for stage in ACCT_STAGES},
    **{f"acct_{stage}_ms.stream": ("stream", ("accounting", stage)) for stage in ACCT_STAGES},
}
# seconds the window adds to every label pair: by position in these lists, so
# that no two pairs rise alike and a reader that took a neighbour's is found out
_STAGE_PAIRS = [("interpret", "setup"), ("interpret", "workers"), ("interpret", "merge"),
                ("lanes", "order"), ("lanes", "shards"), ("digests", "shards"),
                ("accounting", "decide"), ("accounting", "fill"), ("accounting", "copy")]
_FAN_PAIRS = [(call, stat) for call in ("interpret", "lanes", "digests")
              for stat in ("wall", "held", "sum", "max", "start_lag", "tail")]
_STAGE_ROSE = {pair: 0.003 * (i + 1) for i, pair in enumerate(_STAGE_PAIRS)}
_FAN_ROSE = {pair: 0.0007 * (i + 1) for i, pair in enumerate(_FAN_PAIRS)}


def stage_ctx(kind, families=(_STAGE_SECONDS, _FAN_SECONDS), calls=3, n_blocks=4, scale=1.0):
    """A window of `calls` timed connects, or passes of `n_blocks` blocks,
    over which every label pair of the two families rose from 5.0 by its
    `_ROSE` seconds times `scale`."""
    def snap(more):
        out = {"consensus_dispatch_total": {"samples": []}}
        if _STAGE_SECONDS in families:
            out[_STAGE_SECONDS] = {"samples": [
                {"labels": {"call": c, "stage": st}, "value": 5.0 + more * v}
                for (c, st), v in _STAGE_ROSE.items()]}
        if _FAN_SECONDS in families:
            out[_FAN_SECONDS] = {"samples": [
                {"labels": {"call": c, "stat": st}, "value": 5.0 + more * v}
                for (c, st), v in _FAN_ROSE.items()]}
        return out
    return {"cell": "made-up", "trace": None, "driver": {
        "kind": kind, "walls_s": [0.05] * calls, "pass_walls_s": [0.4] * calls,
        "n_inputs": 6, "n_blocks": n_blocks, "phases": [],
        "counters_before": snap(0.0), "counters_after": snap(scale)}}


def _stage_answer(kind, reads, calls=3, n_blocks=4):
    over = calls if kind == "connect" else calls * n_blocks
    first, second = reads
    if second == "share":
        return 100.0 * sum(_FAN_ROSE[c, "sum"] for c in first) / sum(_FAN_ROSE[c, "held"] for c in first)
    if isinstance(first, tuple):
        return sum(_FAN_ROSE[c, second] for c in first) / over * 1000.0
    return _STAGE_ROSE[first, second] / over * 1000.0


@pytest.mark.parametrize("metric", list(STAGE_READERS))
def test_a_stage_reader_takes_its_label_pairs_rise_over_the_timed_calls(metric):
    kind, reads = STAGE_READERS[metric]
    assert reader(metric)(stage_ctx(kind)) == pytest.approx(_stage_answer(kind, reads), rel=1e-9)


@pytest.mark.parametrize("metric", list(STAGE_READERS))
def test_a_stage_reader_returns_none_on_the_parent_and_in_another_kind_of_cell(metric):
    kind, _ = STAGE_READERS[metric]
    other = "stream" if kind == "connect" else "connect"
    assert reader(metric)(stage_ctx(kind, families=())) is None  # the parent: no such family
    assert reader(metric)(stage_ctx(other)) is None
    assert reader(metric)(stage_ctx("serve")) is None
    assert reader(metric)(stage_ctx(kind, calls=0)) is None  # a window that timed no call
    no_snapshots = stage_ctx(kind)
    no_snapshots["driver"]["counters_before"] = no_snapshots["driver"]["counters_after"] = None
    assert reader(metric)(no_snapshots) is None


@pytest.mark.parametrize("metric", ["prep_busy_share.connect", "interp_busy_share.connect"])
def test_a_busy_share_is_none_where_no_thread_time_was_held(metric):
    assert reader(metric)(stage_ctx("connect", scale=0.0)) is None
    assert reader(metric)(stage_ctx("connect", families=(_STAGE_SECONDS,))) is None


def test_benchmark_json_lists_each_new_metric_with_its_cells():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    connect = ["tip-block.cold", "tip-block.warm", "worst-block.sigops", "worst-block-mesh4.sigops",
               "taproot-block.cold",  # PR 39: a new cell is appended to a list, nothing else changed
               "worst-block-multisig20.fanout",  # PR 41
               "worst-block-quadratic.sighash"]  # PR 45
    every = [w["name"] for w in bench["workloads"]]
    want = {
        "unphased_ms.connect": connect, "sig_cache_ms.connect": connect,
        "teardown_ms.connect": connect, "unphased_ms.stream": ["ibd-stream.cold"],
        "take_wait_ms.serve": ["mempool-serve.steady"], "host_ms.serve": ["mempool-serve.steady"],
        "settle_wait_ms.serve": ["mempool-serve.steady"], "ingress_ms.serve": ["mempool-serve.steady"],
        "trace_lower_s.setup": every, "compile_s.setup": every,
        "sighashes_per_input.connect":  # PR 38
            ["worst-block.sigops", "worst-block-mesh4.sigops", "taproot-block.cold",
             "worst-block-multisig20.fanout", "worst-block-quadratic.sighash"],
        # PR 39: the lanes by kind and the taproot hashes of the index path
        "schnorr_lane_share.connect":
            ["taproot-block.cold", "tip-block.cold", "worst-block-quadratic.sighash"],
        "tweak_lane_share.connect": ["taproot-block.cold"],
        "taphashes_per_input.connect": ["taproot-block.cold"],
        # PR 40: the coin tables' probes
        "coin_probes_per_input.connect":
            ["tip-block.cold", "tip-block.warm", "taproot-block.cold", "worst-block.sigops",
             "worst-block-multisig20.fanout", "worst-block-quadratic.sighash"],
        "coin_probes_per_input.stream": ["ibd-stream.cold"],
        # PR 41: the walk's share of the pre-recorded CHECKMULTISIG pairings
        "walk_share_of_spec.connect": ["worst-block-multisig20.fanout", "worst-block.sigops"],
        # PR 42: the dense tile's share of the kernel's grid steps
        "full_tile_share.connect": connect,
        # PR 43: the pieces a one-device dispatch travels in (the mesh cell is packed already)
        "transfers_per_dispatch.stream": ["ibd-stream.cold"],
        "transfers_per_dispatch.connect":
            ["tip-block.cold", "tip-block.warm", "worst-block.sigops", "taproot-block.cold",
             "worst-block-multisig20.fanout", "worst-block-quadratic.sighash"],
        # PR 45: the bytes behind the ECDSA digests, and the rate a thread hashes them at
        "sighash_kb_per_input.connect": ["worst-block-quadratic.sighash", "tip-block.cold"],
        "sighash_mb_per_s.connect": ["worst-block-quadratic.sighash"],
        # PR 48: the reorganisation cell's own
        **{name: ["tip-reorg.depth2"] for name in REORG},
        # PR 50: the native stage clock; the warm cell's precharge runs the session's
        # calls outside its timed connects, its accounting does not
        **{name: STAGE_CELLS for name in SESSION_STAGE_READERS},
        **{f"acct_{stage}_ms.connect": STAGE_CELLS + ["tip-block.warm"] for stage in ACCT_STAGES},
        **{f"acct_{stage}_ms.stream": ["ibd-stream.cold"] for stage in ACCT_STAGES},
    }
    for name, cells in want.items():
        assert by_name[name]["workloads"] == cells, name
        assert os.path.exists(os.path.join(REPO, "benchmarks", "layers", name + ".py"))
    assert [m["name"] for m in bench["per_layer"]][-len(want):] == list(want)
