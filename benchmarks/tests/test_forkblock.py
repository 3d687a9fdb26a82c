"""The fork generator, the plain reference that connects and disconnects,
the reorganisation cell end to end at rehearsal size, and its readers."""

import importlib
import pickle
import subprocess
import sys

import pytest

import run
from benchmarks.harness import reorgref

CELL = "tip-reorg.depth2"
A, B = ("A1", "A2"), ("B1", "B2", "B3")


def _build(seed, rehearsal=True):
    spec = run.load_spec(CELL, rehearsal=rehearsal)
    gen = importlib.import_module(f"benchmarks.generators.{spec['traffic']['generator']}")
    return spec, gen.build(spec["config"], spec["traffic"], seed, 4.0)


def _heights(d):
    h = d["fork_height"]
    return {"A1": h + 1, "A2": h + 2, "B1": h + 1, "B2": h + 2, "B3": h + 3}


def test_same_seed_same_bytes_and_any_seed_same_counts():
    a, b = _build(2**31 + 5)[1], _build(2**31 + 5)[1]
    assert pickle.dumps(a) == pickle.dumps(b)
    c = _build(6)[1]
    assert a["blocks"] != c["blocks"] and a["counts"] == c["counts"]
    assert [len(a["coins"]), len(a["txs"]["B3"])] == [len(c["coins"]), len(c["txs"]["B3"])]


def test_the_branches_share_what_the_configuration_says():
    spec, d = _build(77)
    blk, fork = spec["config"]["block"], spec["config"]["fork"]
    txs = {k: reorgref.parse_block(raw) for k, raw in d["blocks"].items()}
    ids = {k: [t["txid"] for t in v[1:]] for k, v in txs.items()}
    n_out = round(blk["txs"] * fork["left_out_share"])
    for a, b in (("A1", "B1"), ("A2", "B2")):
        # the same transactions in the same places, but for the left-out ones
        differ = [i for i, (x, y) in enumerate(zip(ids[a], ids[b], strict=True)) if x != y]
        assert len(differ) == n_out
        assert not set(ids[a][i] for i in differ) & set(ids[b])
    left = (set(ids["A1"]) - set(ids["B1"])) | (set(ids["A2"]) - set(ids["B2"]))
    assert left <= set(ids["B3"]) and len(left) == 2 * n_out
    assert not (set(ids["B3"]) - left) & (set(ids["A1"]) | set(ids["A2"]))
    # A2's in-branch spends name outputs A1 made, in transactions B2 holds too
    made = {(t["txid"], n) for t in txs["A1"] for n in range(len(t["vout"]))}
    takers = [t for t in txs["A2"][1:] if made & set(t["vin"])]
    assert sum(len(made & set(t["vin"])) for t in takers) == fork["in_branch_spends"]
    assert all(t["txid"] in ids["B2"] for t in takers)
    # the order of sizes differs, so that A1's record cannot fit A2 by its counts
    assert [len(t["vin"]) for t in txs["A1"]] != [len(t["vin"]) for t in txs["A2"]]


def test_the_reference_reorganises_and_refuses():
    _, d = _build(78)
    h, blocks = _heights(d), d["blocks"]
    ref = reorgref.ReorgRef(d["coins"])
    at_fork = dict(ref.coins)
    undo = {k: ref.connect(blocks[k], h[k]) for k in A}
    at_tip_a = dict(ref.coins)
    assert ref.disconnect(blocks["A2"], undo["A1"], h["A2"]) == "failed"
    assert ref.disconnect(blocks["A1"], undo["A1"], h["A1"]) == "unclean"
    assert ref.disconnect(blocks["A2"], undo["A2"], h["A2"] + 1) == "unclean"  # another height
    assert ref.disconnect(blocks["A2"], undo["A2"][:-1], h["A2"]) == "failed"
    assert ref.coins == at_tip_a  # nothing a refusal touched
    assert ref.disconnect(blocks["A2"], undo["A2"], h["A2"]) == "ok"
    assert ref.disconnect(blocks["A2"], undo["A2"], h["A2"]) == "unclean"
    assert ref.disconnect(blocks["A1"], undo["A1"], h["A1"]) == "ok"
    assert ref.coins == at_fork
    undo.update({k: ref.connect(blocks[k], h[k]) for k in B})
    c = d["counts"]
    assert len(ref.coins) == len(at_fork) + sum(c["outputs"][k] - c["inputs"][k] for k in B)
    for k in reversed(B):
        assert ref.disconnect(blocks[k], undo[k], h[k]) == "ok"
    assert ref.coins == at_fork
    with pytest.raises(KeyError):
        ref.connect(blocks["A2"], h["A2"])  # A1's outputs are missing
    with pytest.raises(ValueError):
        reorgref.parse_block(blocks["A1"] + b"\x00")
    with pytest.raises(ValueError):
        reorgref.parse_block(blocks["A1"][:-3])


def test_the_reference_reads_blocks_with_and_without_witnesses():
    """The pre-segwit chain of `ibd-stream` and this cell's witness blocks,
    against the program's own parser: the same txids, inputs and outputs."""
    from bitcoinconsensus_tpu.core.block import Block

    chain_spec = run.load_spec("ibd-stream.cold", rehearsal=True)
    chain = importlib.import_module("benchmarks.generators.chain").build(
        chain_spec["config"], chain_spec["traffic"], 5, 4.0)
    for raw in (chain["blocks"][0], _build(79)[1]["blocks"]["B3"]):
        mine, theirs = reorgref.parse_block(raw), Block.deserialize(raw).vtx
        assert [t["txid"] for t in mine] == [t.txid for t in theirs]
        assert [t["vin"] for t in mine] == [[(i.prevout.hash, i.prevout.n) for i in t.vin]
                                            for t in theirs]
        assert [t["vout"] for t in mine] == [[(o.value, o.script_pubkey) for o in t.vout]
                                             for t in theirs]


def test_the_reference_agrees_with_the_programs_view():
    from bitcoinconsensus_tpu import native_bridge

    _, d = _build(80)
    h, blocks = _heights(d), d["blocks"]
    view = native_bridge.NativeCoinsView()
    view.add_coins_batch(d["coins"])
    ref = reorgref.ReorgRef(d["coins"])
    records = {}
    for k in A:
        records[k] = (view.apply_block(native_bridge.NativeBlock(blocks[k]), h[k], undo=True),
                      ref.connect(blocks[k], h[k]))
        assert ref.differences(view, 0) == []
    for k in reversed(A):
        got = view.disconnect_block(native_bridge.NativeBlock(blocks[k]), records[k][0], h[k])
        assert got[0] == ref.disconnect(blocks[k], records[k][1], h[k]) == "ok"
        assert ref.differences(view, 0) == []
    view.apply_block(native_bridge.NativeBlock(blocks["B1"]), h["B1"])
    assert len(ref.differences(view, 0)) == 5  # the count, and coins of B1


def test_full_size_counts():
    spec, d = _build(2**31 + 12, rehearsal=False)
    c = d["counts"]
    every = dict.fromkeys(A + B, 6000)
    assert c["inputs"] == every and len(d["coins"]) == c["funded"] == 17_700
    assert c["outputs"] == {"A1": 2702, "A2": 2402, "B1": 2702, "B2": 2402, "B3": 2402}
    assert c["new_inputs"] == {"B1": 300, "B2": 300, "B3": 5400}
    # a 512-lane tile each, and one 8,192-lane dispatch
    assert c["new_lanes"] == {"B1": 390, "B2": 390, "B3": 7020}
    assert c["cold_lanes"] == {"A1": 7800, "A2": 7800}
    assert c["in_branch_spends"] == {"A2": 300, "B2": 300, "B3": 0}
    assert spec["config"]["fork"]["verdicts_a_reorganisation"] == sum(c["inputs"][k] for k in B)
    want_sizes = sorted(
        int(s) for s, n in spec["config"]["block"]["inputs_per_tx"].items() for _ in range(n))
    for k in B:
        assert sorted(len(t["outs"]) for t in d["txs"][k]) == want_sizes
    assert all(900_000 < len(raw) < 1_100_000 for raw in d["blocks"].values())
    # what `undo_probes_per_input.reorg` has to read
    assert (12_000 + c["outputs"]["A1"] + c["outputs"]["A2"]) / 12_000 == pytest.approx(1.4253333)
    # and `coin_probes_per_input.reorg`: three probes an input and an output of B's blocks
    assert 3 * (18_000 + sum(c["outputs"][k] for k in B)) / 18_000 == pytest.approx(4.251)


def test_the_rehearsal_end_to_end_and_a_control():
    def rehearse(*extra):
        return subprocess.run(
            [sys.executable, "benchmarks/rehearse.py", "--workload", CELL, "--seed", "9",
             "--seconds", "2", *extra], cwd=run.ROOT, capture_output=True, text=True, timeout=900)

    sound = rehearse()
    assert sound.returncode == 0 and '"correct": true' in sound.stdout, sound.stdout[-2000:]
    assert '"would_report": ["inputs_per_s", "setup_s"]' in sound.stdout
    broken = rehearse("--control", "truth-shift")
    assert '"correct": false' in broken.stdout, broken.stdout[-2000:]


def _ctx(**driver):
    base = {
        "kind": "reorg", "walls_s": [0.10, 0.12], "disconnect_s": [0.008, 0.010, 0.009, 0.011],
        "gaps_s": [[0.030, 0.020, 0.040], [0.034, 0.022, 0.044]],
        "phases": [{"sync": {"secs": 0.004, "outer_secs": 0.004},
                    "undo": {"secs": 0.003, "outer_secs": 0.003}},
                   {"sync": {"secs": 0.006, "outer_secs": 0.006},
                    "undo": {"secs": 0.003, "outer_secs": 0.003}}],
        "deltas": [{"undo_probes": 17104.0, "connect_probes": 76518.0,
                    "consensus_cache_hits_total": 12000.0,
                    "consensus_cache_lookups_total": 20000.0,
                    "consensus_dispatch_total": 3.0,
                    "consensus_dispatch_transfers_total": 6.0}] * 2,
        "disconnected_inputs": 12000, "verdicts": 18000,
    }
    return {"driver": {**base, **driver}, "trace": None}


def test_reorg_readers():
    ctx = _ctx()
    assert run.load_reader("disconnect_ms.reorg")(ctx) == pytest.approx(9.5)
    assert run.load_reader("undo_probes_per_input.reorg")(ctx) == pytest.approx(17104 / 12000)
    assert run.load_reader("cache_hit_share.reorg")(ctx) == pytest.approx(60.0)
    assert run.load_reader("warm_result_gap_ms.reorg")(ctx) == pytest.approx(26.0)
    assert run.load_reader("fresh_result_gap_ms.reorg")(ctx) == pytest.approx(42.0)
    assert run.load_reader("settle_wait_ms.reorg")(ctx) == pytest.approx(5.0)
    assert run.load_reader("host_ms.reorg")(ctx) == pytest.approx(3.0)
    assert run.load_reader("unphased_ms.reorg")(ctx) == pytest.approx(102.0)  # 93 and 111
    assert run.load_reader("coin_probes_per_input.reorg")(ctx) == pytest.approx(4.251)
    assert run.load_reader("transfers_per_dispatch.reorg")(ctx) == pytest.approx(2.0)
    for name in ("kernel_ms.reorg", "device_idle.reorg", "overlap_share.reorg"):  # no trace
        assert run.load_reader(name)(ctx) is None
    ctx["trace"] = {"busy_s": 1.0, "window_s": 4.0, "within": {"bench.reorg": {
        "count": 4, "span_s": 0.5, "busy_s": 0.04,
        "modules": {"jit_packed_verify_tiles(1)": 0.032, "jit_other": 1.0},
    }}}
    assert run.load_reader("kernel_ms.reorg")(ctx) == pytest.approx(8.0)
    assert run.load_reader("device_idle.reorg")(ctx) == pytest.approx(92.0)
    assert run.load_reader("overlap_share.reorg")(ctx) == pytest.approx(37.5)  # 5 of 8 ms waited for
    metrics = ("disconnect_ms", "undo_probes_per_input", "cache_hit_share", "warm_result_gap_ms",
               "fresh_result_gap_ms", "settle_wait_ms", "kernel_ms", "device_idle", "host_ms",
               "coin_probes_per_input", "unphased_ms", "overlap_share", "transfers_per_dispatch")
    # another kind of cell, and a window that timed nothing: nothing to read
    for other in ({"driver": {"kind": "stream", "phases": []}, "trace": ctx["trace"]},
                  {"driver": {**ctx["driver"], "walls_s": []}, "trace": ctx["trace"]}):
        assert all(run.load_reader(m + ".reorg")(other) is None for m in metrics)
    # a program without the probe counter's `undo` table leaves that one out
    bare = _ctx(deltas=[{"consensus_cache_hits_total": 1.0, "consensus_cache_lookups_total": 0.0}])
    assert run.load_reader("undo_probes_per_input.reorg")(bare) is None
    assert run.load_reader("cache_hit_share.reorg")(bare) is None  # no lookup
    assert run.load_reader("coin_probes_per_input.reorg")(bare) is None
    assert run.load_reader("transfers_per_dispatch.reorg")(bare) is None
