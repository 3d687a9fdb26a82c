"""The block at the sigop limit: what the generator makes, the plain
reference against walks written out by hand, and the cell end to end at
rehearsal size, sound and under each control."""

import hashlib
import importlib
import pickle

import pytest

import run
from benchmarks.harness import chipguard, ec, ecverify, msigner, sigopref
from benchmarks.harness.cell import CONTROLS

CELL = "worst-block.sigops"


def _build(seed, rehearsal=True):
    spec = run.load_spec(CELL, rehearsal=rehearsal)
    gen = importlib.import_module(f"benchmarks.generators.{spec['traffic']['generator']}")
    return spec, gen.build(spec["config"], spec["traffic"], seed, 4.0)


# -- the generator ---------------------------------------------------------------

def test_same_seed_same_bytes_and_any_seed_same_counts():
    _, a = _build(2**31 + 5)
    assert pickle.dumps(a) == pickle.dumps(_build(2**31 + 5)[1])
    spec, b = _build(6)
    assert a["block"] != b["block"]
    blk = spec["config"]["block"]
    for d in (a, b):
        assert d["n_inputs"] == blk["inputs"] == len(d["coins"]) == 15
        assert len(d["txs"]) == blk["txs"] == 3
        assert all(len(t["outs"]) == blk["inputs_per_tx"] for t in d["txs"])
        assert d["sigop_cost"] == blk["sigop_cost"] == 15 * 20
        assert d["pairings"] == 15 * 20 and d["unseen_txs"] == []
        assert 0 <= d["victim"] < 15 and d["bad_tx"]["index"] == d["victim"] // 5
    assert abs(a["weight"] - b["weight"]) <= 4 * 15  # a DER signature is 70 to 72 bytes


def test_each_input_is_a_1_of_20_signed_by_the_first_pushed_key():
    _, d = _build(9)
    keys = set()
    for t in d["txs"]:
        tx = sigopref.parse_tx(t["raw"])
        assert len(tx.vout) == 1 and len(tx.vout[0][1]) == 22  # one P2WPKH output
        for i, txin in enumerate(tx.vin):
            dummy, sig, script = txin.witness
            assert dummy == b"" and len(script) == 684 and sig[-1] == 1  # SIGHASH_ALL
            m, pubs = sigopref.parse_bare_multisig(script)
            assert (m, len(pubs)) == (1, 20) and all(len(p) == 33 for p in pubs)
            keys.update(pubs)
            tried, ok = sigopref.p2wsh_multisig_input(tx, i, t["outs"][i])
            # the walk tries the last-pushed key first and the signer's last
            assert ok and tried == [(0, k) for k in range(19, -1, -1)]
    assert len(keys) == 15 * 20
    # the corrupted twin: the victim's signature verifies against no key
    bad = d["bad_tx"]
    tx = sigopref.parse_tx(bad["raw"])
    index = d["victim"] - d["tx_start"][bad["index"]]
    tried, ok = sigopref.p2wsh_multisig_input(tx, index, bad["outs"][index])
    assert not ok and len(tried) == 20


def test_full_size_is_at_the_limit():
    spec, d = _build(2**31 + 77, rehearsal=False)
    assert (d["n_inputs"], len(d["txs"]), d["sigop_cost"], d["pairings"]) == (4000, 160, 80000, 80000)
    assert 3_700_000 < d["weight"] < 4_000_000 and 3_100_000 < len(d["block"]) < 3_300_000
    assert spec["config"]["reduced"] == [] and spec["config"]["oracle_sample"] == 64


# -- the plain reference, against walks written out by hand -------------------------

KEYS = [bytes([2]) + bytes([k]) * 32 for k in range(1, 21)]


def _oracle(true_pairs):
    return lambda sig, key: (sig, key) in true_pairs


@pytest.mark.parametrize("signer,tried", [
    (0, [(0, k) for k in range(19, -1, -1)]),   # key 1: all twenty, the last one holds
    (9, [(0, k) for k in range(19, 8, -1)]),    # key 10: eleven
    (19, [(0, 19)]),                              # key 20: the first one holds
])
def test_walk_of_a_1_of_20(signer, tried):
    got, ok = sigopref.multisig_walk(1, KEYS, [b"s"], _oracle({(b"s", KEYS[signer])}))
    assert ok and got == tried
    got, ok = sigopref.multisig_walk(1, KEYS, [b"s"], _oracle(set()))
    assert not ok and got == [(0, k) for k in range(19, -1, -1)]


def test_walk_of_a_2_of_3():
    a, b, c = KEYS[:3]
    # signed by keys 1 and 2: sig 2 fails key 3, holds key 2; sig 1 holds key 1
    got, ok = sigopref.multisig_walk(2, [a, b, c], [b"s1", b"s2"], _oracle({(b"s1", a), (b"s2", b)}))
    assert ok and got == [(1, 2), (1, 1), (0, 0)]
    # signed by keys 2 and 3: two pairings, both hold
    got, ok = sigopref.multisig_walk(2, [a, b, c], [b"s2", b"s3"], _oracle({(b"s2", b), (b"s3", c)}))
    assert ok and got == [(1, 2), (0, 1)]
    # signatures in the wrong order: sig for key 1 pushed last; it meets keys
    # 3, 2 and fails, and then two signatures are left for one key
    got, ok = sigopref.multisig_walk(2, [a, b, c], [b"s2", b"s1"], _oracle({(b"s1", a), (b"s2", b)}))
    assert not ok and got == [(1, 2), (1, 1)]


def test_sigop_counts():
    script = msigner.multisig_script(1, KEYS)
    assert len(script) == 684 and script[-3:-1] == b"\x01\x14"  # 20 is pushed as data
    assert sigopref.script_sigops(script, accurate=True) == 20
    assert sigopref.script_sigops(msigner.multisig_script(2, KEYS[:3]), accurate=True) == 3
    assert sigopref.script_sigops(msigner.multisig_script(2, KEYS[:3]), accurate=False) == 20
    p2pkh = b"\x76\xa9\x14" + b"\x11" * 20 + b"\x88\xac"
    assert sigopref.script_sigops(p2pkh, accurate=False) == 1

    def tx(script_sig=b"", witness=(), out_spk=b"\x00\x14" + b"\x22" * 20):
        return sigopref.Tx(2, [sigopref.TxIn(b"\x33" * 32, 0, script_sig, 0xFFFFFFFF, list(witness))],
                           [(1000, out_spk)], 0)

    # a witness program's count x 1; the legacy count of the outputs x 4
    assert sigopref.tx_sigop_cost(tx(witness=[b"", b"s", script]), [(5000, msigner.p2wsh(script))]) == 20
    assert sigopref.tx_sigop_cost(tx(witness=[b"s", KEYS[0]]), [(5000, b"\x00\x14" + b"\x44" * 20)]) == 1
    assert sigopref.tx_sigop_cost(tx(out_spk=p2pkh), [(5000, p2pkh)]) == 4
    # P2SH: the redeem script's accurate count x 4; P2SH-wrapped P2WSH: the witness script's x 1
    redeem = msigner.multisig_script(2, KEYS[:3])
    p2sh = b"\xa9\x14" + b"\x55" * 20 + b"\x87"
    assert sigopref.tx_sigop_cost(tx(script_sig=b"\x4c" + bytes([len(redeem)]) + redeem), [(5000, p2sh)]) == 12
    wrapped = msigner.p2wsh(script)
    assert sigopref.tx_sigop_cost(
        tx(script_sig=bytes([len(wrapped)]) + wrapped, witness=[b"", b"s", script]), [(5000, p2sh)]) == 20
    # a coinbase: its own scripts only
    assert sigopref.tx_sigop_cost(tx(out_spk=b"\x51"), []) == 0


def test_reference_agrees_with_the_program_where_both_speak():
    """The reader, the digest, the curve and the count against the program's
    own, on the generator's block: two implementations, one answer."""
    from bitcoinconsensus_tpu.core.flags import height_to_flags
    from bitcoinconsensus_tpu.core.sighash import SIGHASH_ALL, bip143_sighash
    from bitcoinconsensus_tpu.core.tx import Tx, TxOut
    from bitcoinconsensus_tpu.crypto import secp_host
    from bitcoinconsensus_tpu.models.validate import get_transaction_sigop_cost

    _, d = _build(12)
    flags = height_to_flags(d["height"], extended=True)
    record = d["txs"][1]
    theirs, ours = Tx.deserialize(record["raw"]), sigopref.parse_tx(record["raw"])
    spent = [TxOut(a, s) for a, s in record["outs"]]
    assert sigopref.tx_sigop_cost(ours, record["outs"]) == get_transaction_sigop_cost(theirs, spent, flags) == 100
    script = ours.vin[2].witness[-1]
    digest = sigopref.bip143_digest_all(ours, 2, script, record["outs"][2][0])
    assert digest == bip143_sighash(script, theirs, 2, SIGHASH_ALL, record["outs"][2][0])
    _, keys = sigopref.parse_bare_multisig(script)
    sig = ours.vin[2].witness[1][:-1]
    for k in (0, 1, 19):
        assert ecverify.verify_ecdsa(keys[k], sig, digest) == secp_host.verify_ecdsa(keys[k], sig, digest) == (k == 0)


def test_key_runs_are_the_keys_of_their_secrets():
    bases = msigner.run_bases("t", 3, 20)
    runs = msigner.key_runs(bases, 20)
    assert [len(r) for r in runs] == [20, 20, 20]
    for base, run_ in zip(bases, runs):
        for j in (0, 1, 19):
            assert run_[j] == ec.pubkey_create(base + j)
    digest = hashlib.sha256(b"m").digest()
    assert ecverify.verify_ecdsa(runs[1][7], ec.sign_ecdsa(bases[1] + 7, digest), digest)
    assert not ecverify.verify_ecdsa(runs[1][8], ec.sign_ecdsa(bases[1] + 7, digest), digest)


# -- the cell, at rehearsal size -------------------------------------------------------

def _run(control, seed):
    spec = run.load_spec(CELL, rehearsal=True)
    dev = dict(chipguard.device_info(), count=1)
    return run.run_cell(spec, seed, 2.0, False, dev, control=control)


def test_sound_run_is_correct():
    line = _run(None, 2**31 + 41)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"connect_ms_p50", "inputs_per_s", "setup_s"}
    detail = line["detail"]
    assert detail["sigop_cost"] == [300] and detail["pairings"] == 300
    assert detail["spec_pairings_a_connect"] == 300
    assert detail["phase_ms_p50"]["backpressure"] > 0  # five chunks, a queue four deep


@pytest.mark.parametrize("control", CONTROLS)
def test_broken_run_is_not_correct(control):
    assert _run(control, 43)["correct"] is False


def test_readers_read_a_connect_and_nothing_else():
    """Each new reader on a hand-made context, and on one from a program
    that lacks the phase and the counter."""
    ctx = {"driver": {
        "kind": "connect", "n_inputs": 10, "walls_s": [0.5, 0.5],
        "phases": [{"sync": {"secs": 0.05}, "backpressure": {"secs": 0.03}}] * 2,
        "deltas": [{"consensus_dispatch_total": 3, "consensus_dispatch_lanes_total": 200}] * 2,
        "counters_before": {"consensus_fixpoint_reinterpreted_inputs_total": {"samples": [{"labels": {}, "value": 4}]}},
        "counters_after": {"consensus_fixpoint_reinterpreted_inputs_total": {"samples": [{"labels": {}, "value": 24}]}},
    }, "trace": {"within": {"bench.connect": {"count": 2, "modules": {"jit_verify_tiles": 0.4}}}}}
    read = {n: run.load_reader(n) for n in (
        "dispatches.connect", "checks_per_input.connect", "backpressure_ms.connect",
        "reinterpret_share.connect", "overlap_share.connect")}
    assert read["dispatches.connect"](ctx) == 3
    assert read["checks_per_input.connect"](ctx) == 20
    assert read["backpressure_ms.connect"](ctx) == pytest.approx(30.0)
    assert read["reinterpret_share.connect"](ctx) == pytest.approx(100.0)
    assert read["overlap_share.connect"](ctx) == pytest.approx(60.0)  # 1 - 80 / 200
    old = {"driver": {**ctx["driver"], "phases": [{"sync": {"secs": 0.05}}] * 2,
                      "counters_before": {}, "counters_after": {}}, "trace": None}
    assert read["backpressure_ms.connect"](old) is None
    assert read["reinterpret_share.connect"](old) is None
    assert read["overlap_share.connect"](old) is None  # no trace, no kernel time
    for name, reader in read.items():
        assert reader({"driver": {"kind": "served"}, "trace": None}) is None, name
