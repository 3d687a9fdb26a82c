"""The chain generator, the plain chain reference and the stream readers."""

import importlib
import pickle
from collections import Counter

import pytest

import run
from benchmarks.harness import chainref

CELL = "ibd-stream.cold"


def _build(seed, rehearsal=True):
    spec = run.load_spec(CELL, rehearsal=rehearsal)
    gen = importlib.import_module(f"benchmarks.generators.{spec['traffic']['generator']}")
    return spec, gen.build(spec["config"], spec["traffic"], seed, 4.0)


def _shape(d):
    """What no seed may change."""
    per_block = []
    for k in range(d["n_blocks"]):
        sizes = sorted(len(t["outs"]) for t in d["txs"][k])
        per_block.append((len(d["txs"][k]), sum(sizes), sizes, Counter(d["kinds"][k])))
    return per_block, len(d["coins"]), d["bad"]["index"], d["start_height"]


def test_same_seed_same_bytes_and_any_seed_same_shape():
    a, b = _build(2**31 + 5)[1], _build(2**31 + 5)[1]
    assert pickle.dumps(a) == pickle.dumps(b)
    c = _build(6)[1]
    assert a["blocks"] != c["blocks"] and _shape(a) == _shape(c)


def test_the_reference_follows_the_chain_and_the_blocks_depend_on_each_other():
    spec, d = _build(77)
    ch = spec["config"]["chain"]
    ref = chainref.ChainRef(d["coins"])
    funded = set(ref.coins)
    for k, raw in enumerate(d["blocks"]):
        before = set(ref.coins)
        txs = chainref.parse_block(raw)
        assert len(txs) == ch["txs"] and len(raw) < ch["max_block_bytes"]
        spends = [op for tx in txs[1:] for op in tx["vin"]]
        assert len(spends) == ch["inputs"] == d["n_inputs"]
        in_stream = [op for op in spends if op not in funded]
        # After the first block, so many inputs spend what the block before
        # paid forward: P2PKH outputs that are in the reference by then.
        assert len(in_stream) == (ch["in_stream_spends"] if k else 0)
        assert all(op in before and ref.coins[op][1][:3] == b"\x76\xa9\x14" for op in in_stream)
        ref.apply(raw, d["start_height"] + k)
    assert funded <= ref.spent  # every funded coin is spent by the end
    assert len(ref.coins) == ch["blocks"] * ch["txs"] + ch["in_stream_spends"]
    # The corrupted block differs from the good one in one transaction.
    at = d["bad"]["index"]
    good, bad = chainref.parse_block(d["blocks"][at]), chainref.parse_block(d["bad"]["block"])
    differ = [t for t, (g, b) in enumerate(zip(good, bad)) if g["txid"] != b["txid"]]
    assert differ == [0, d["bad"]["tx"]["index"] + 1] or differ == [d["bad"]["tx"]["index"] + 1]
    assert [g["vin"] for g in good] == [b["vin"] for b in bad]


def test_the_reference_refuses_what_it_cannot_read():
    _, d = _build(78)
    with pytest.raises(ValueError):
        chainref.parse_block(d["blocks"][0] + b"\x00")
    with pytest.raises(ValueError):
        chainref.parse_block(d["blocks"][0][:-3])
    ref = chainref.ChainRef(d["coins"])
    with pytest.raises(KeyError):
        ref.apply(d["blocks"][1], d["start_height"] + 1)  # block 0's outputs are missing


def test_the_reference_agrees_with_the_programs_view():
    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.core.tx import OutPoint

    _, d = _build(79)
    view = native_bridge.NativeCoinsView()
    view.add_coins_batch(d["coins"])
    ref = chainref.ChainRef(d["coins"])
    for k, raw in enumerate(d["blocks"]):
        view.apply_block(native_bridge.NativeBlock(raw), d["start_height"] + k)
        ref.apply(raw, d["start_height"] + k)
        assert ref.differences(view, 0) == []
    some = next(iter(ref.coins))
    view.spend(OutPoint(*some))
    assert len(ref.differences(view, 0)) == 2  # the count, and the coin


def test_full_size_counts():
    spec, d = _build(2**31 + 12, rehearsal=False)
    shape, n_coins, bad_index, start = _shape(d)
    assert (len(shape), n_coins, bad_index, start) == (8, 45_900, 4, 419_324)
    want_sizes = sorted(
        int(s) for s, n in spec["config"]["chain"]["inputs_per_tx"].items() for _ in range(n))
    for n_txs, n_inputs, sizes, kinds in shape:
        assert (n_txs, n_inputs) == (1556, 6000) and sizes == want_sizes
        assert kinds == {"p2pkh": 5700, "p2sh_multisig": 300}
    assert all(900_000 < len(raw) < 1_000_000 for raw in d["blocks"])
    assert len(chainref.parse_block(d["blocks"][3])) == 1557


def _ctx(**driver):
    return {"driver": {"kind": "stream", "n_blocks": 2, **driver}, "trace": None}


def test_stream_readers():
    phases = [{"sync": 0.004, "interpret": 0.010, "apply": 0.002},
              {"sync": 0.006, "interpret": 0.020}]
    ctx = _ctx(phases=phases, block_gaps_s=[0.05, 0.07, 0.09], counters_before=None)
    assert run.load_reader("block_ms_p50.stream")(ctx) == pytest.approx(70.0)
    assert run.load_reader("host_ms.stream")(ctx) == pytest.approx(16.0)
    assert run.load_reader("settle_wait_ms.stream")(ctx) == pytest.approx(5.0)
    assert run.load_reader("blocks_in_flight.stream")(ctx) is None
    # No trace: the trace's readers have nothing to read.
    for name in ("kernel_ms.stream", "device_idle.stream", "overlap_share.stream"):
        assert run.load_reader(name)(ctx) is None
    # 2 passes of 2 blocks in the slice, 0.08 s of the two programs inside them.
    ctx["trace"] = {"busy_s": 1.0, "window_s": 4.0, "within": {"bench.stream": {
        "count": 2, "span_s": 0.4, "busy_s": 0.1,
        "modules": {"jit_verify_tiles(1)": 0.07, "jit__verdict_checksum(2)": 0.01, "jit_other": 1.0},
    }}}
    assert run.load_reader("kernel_ms.stream")(ctx) == pytest.approx(20.0)
    assert run.load_reader("device_idle.stream")(ctx) == pytest.approx(75.0)
    assert run.load_reader("overlap_share.stream")(ctx) == pytest.approx(75.0)
    ctx["driver"]["phases"] = [{"sync": 0.030}]
    assert run.load_reader("overlap_share.stream")(ctx) == 0.0  # floored
    # In another kind of cell every one of them has nothing to read.
    other = {"driver": {"kind": "connect", "phases": phases}, "trace": ctx["trace"]}
    for m in ("block_ms_p50", "host_ms", "settle_wait_ms", "blocks_in_flight", "kernel_ms",
              "device_idle", "overlap_share"):
        assert run.load_reader(m + ".stream")(other) is None
