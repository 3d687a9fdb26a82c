"""The block of the taproot era: what the generator makes, the plain
BIP 341/342 reference against BIP 340's vectors and the benchmark's own
signatures, and the cell end to end at rehearsal size, sound and under each
control."""

import importlib
import os
import pickle
import subprocess
import sys

import pytest

import run
from benchmarks.generators import tapblock
from benchmarks.harness import chipguard, ec, schnorrverify, sigopref, tapref, tapsigner
from benchmarks.harness.cell import CONTROLS

CELL = "taproot-block.cold"


def _build(seed, rehearsal=True):
    spec = run.load_spec(CELL, rehearsal=rehearsal)
    gen = importlib.import_module(f"benchmarks.generators.{spec['traffic']['generator']}")
    return spec, gen.build(spec["config"], spec["traffic"], seed, 4.0)


# -- the generator ---------------------------------------------------------------

def test_same_seed_same_bytes_and_any_seed_same_counts():
    _, a = _build(2**31 + 5)
    assert pickle.dumps(a) == pickle.dumps(_build(2**31 + 5)[1])
    spec, b = _build(6)
    assert a["block"] != b["block"] and a["coins"] != b["coins"]  # other keys
    blk = spec["config"]["block"]
    for d in (a, b):
        assert d["n_inputs"] == blk["inputs"] == len(d["coins"]) == 60
        assert len(d["txs"]) == blk["txs"] == 24
        assert sorted(len(t["outs"]) for t in d["txs"]) == [1] * 12 + [2] * 4 + [3] * 4 + [7] * 4
        assert {k: d["kinds"].count(k) for k in set(d["kinds"])} == blk["inputs_by_kind"]
        assert d["lanes_by_kind"] == blk["lanes_by_kind"] == {"ecdsa": 9, "schnorr": 57, "tweak": 12}
        assert d["sigop_cost"] == blk["sigop_cost"] == 9 and d["unseen_txs"] == []
        assert [t["name"] for t in d["twins"]] == list(tapsigner.CORRUPTIONS)
    assert abs(a["weight"] - b["weight"]) <= 4 * 9  # a DER signature is 70 to 72 bytes


EMPTIES = set()  # over the seeds below: which key of a 2-of-3 did not sign


@pytest.mark.parametrize("seed", [9, 2**31 + 10])
def test_every_input_passes_the_reference_and_every_twin_fails_where_it_should(seed):
    _, d = _build(seed)
    empties, at = EMPTIES, 0
    for t in d["txs"]:
        spend = tapref.Spend(t["raw"], t["outs"])
        assert len(spend.tx.vout) == 1 and spend.tx.vout[0][1][:2] == b"\x51\x20"  # one P2TR output
        for i, txin in enumerate(spend.tx.vin):
            kind, verdict = d["kinds"][at], spend.verify(i)
            assert verdict.ok and verdict.error == "OK", (at, kind, verdict)
            assert verdict.checks == dict(zip(tapref.KINDS, tapblock.CHECKS[kind]))
            if kind == "p2tr_csa_2of3":
                sigs = txin.witness[:3][::-1]  # script order
                assert sorted(map(len, sigs)) == [0, 64, 64] and len(txin.witness[-1]) == 97
                assert len(txin.witness[-2]) == 104 and txin.witness[-2][-2:] == b"\x52\x9c"
                empties.add(sigs.index(b""))
            elif kind == "p2tr_leaf_1":
                assert [len(w) for w in txin.witness] == [64, 34, 97]
            elif kind == "p2tr_key":
                assert [len(w) for w in txin.witness] == [64]
            at += 1
    if seed != 9:
        assert empties == {0, 1, 2}  # the key that does not sign is drawn over all three
    for twin in d["twins"]:
        tx = twin["tx"]
        spend = tapref.Spend(tx["raw"], tx["outs"])
        index = twin["victim"] - d["tx_start"][tx["index"]]
        for i in range(len(tx["outs"])):
            v = spend.verify(i)
            assert (v.ok, v.error) == ((False, twin["error"]) if i == index else (True, "OK"))
    assert d["twins"][1]["error"] == "WITNESS_PROGRAM_MISMATCH"
    assert d["twins"][1]["kind"] in tapsigner.KINDS
    assert (d["twins"][2]["kind"], d["twins"][2]["error"]) == ("p2tr_csa_2of3", "EVAL_FALSE")


def test_full_size_is_at_the_weight_limit():
    spec, d = _build(2**31 + 77, rehearsal=False)
    assert (d["n_inputs"], len(d["txs"]), d["sigop_cost"]) == (10800, 4320, 1620)
    assert d["lanes_by_kind"] == {"ecdsa": 1620, "schnorr": 10260, "tweak": 2160}
    assert sum(d["lanes_by_kind"].values()) == 14040
    assert 3_800_000 <= d["weight"] <= 4_000_000 and 1_850_000 < len(d["block"]) < 1_950_000
    assert spec["config"]["reduced"] == [] and spec["config"]["oracle_sample"] == 256


# -- the plain reference -----------------------------------------------------------

# BIP 340 test vectors 0-3 (public key, message, signature); all verify.
BIP340 = [
    ("F9308A019258C31049344F85F89D5229B531C845836F99B08601F113BCE036F9",
     "0000000000000000000000000000000000000000000000000000000000000000",
     "E907831F80848D1069A5371B402410364BDF1C5F8307B0084C55F1CE2DCA8215"
     "25F66A4A85EA8B71E482A74F382D2CE5EBEEE8FDB2172F477DF4900D310536C0"),
    ("DFF1D77F2A671C5F36183726DB2341BE58FEAE1DA2DECED843240F7B502BA659",
     "243F6A8885A308D313198A2E03707344A4093822299F31D0082EFA98EC4E6C89",
     "6896BD60EEAE296DB48A229FF71DFE071BDE413E6D43F917DC8DCF8C78DE3341"
     "8906D11AC976ABCCB20B091292BFF4EA897EFCB639EA871CFA95F6DE339E4B0A"),
    ("DD308AFEC5777E13121FA72B9CC1B7CC0139715309B086C960E18FD969774EB8",
     "7E2D58D8B3BCDF1ABADEC7829054F90DDA9805AAB56C77333024B9D0A508B75C",
     "5831AAEED7B44BB74E5EAB94BA9D4294C49BCF2A60728D8B4C200F50DD313C1B"
     "AB745879A5AD954A72C45A91C3A51D3C7ADEA98D82F8481E0E1E03674A6F3FB7"),
    ("25D1DFF95105F5253C4022F628A996AD3A0D95FBF21D468A1B33F8C160D8F517",
     "FFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF",
     "7EB0509757E246F19449885651611CB965ECC1A187DD51B64FDA1EDC9637D5EC"
     "97582B9CB13DB3933705B32BA982AF5AF25FD78881EBB32771FC5922EFC66EA3"),
]


@pytest.mark.parametrize("index", range(len(BIP340)))
def test_bip340_vectors(index):
    key, msg, sig = (bytes.fromhex(h) for h in BIP340[index])
    assert schnorrverify.verify_schnorr(key, sig, msg)
    assert not schnorrverify.verify_schnorr(key, sig[:40] + bytes([sig[40] ^ 1]) + sig[41:], msg)
    assert not schnorrverify.verify_schnorr(key, sig, msg[:-1] + bytes([msg[-1] ^ 1]))


def test_verification_refuses_what_bip340_refuses():
    key, msg, sig = (bytes.fromhex(h) for h in BIP340[1])
    p, n = ec.P.to_bytes(32, "big"), ec.N.to_bytes(32, "big")
    assert not schnorrverify.verify_schnorr(p, sig, msg)                # a key not below p
    assert not schnorrverify.verify_schnorr(key, p + sig[32:], msg)     # r not below p
    assert not schnorrverify.verify_schnorr(key, sig[:32] + n, msg)     # s not below n
    assert not schnorrverify.verify_schnorr(key, sig + b"\x00", msg)
    assert schnorrverify.lift_x(5) is None                              # x^3 + 7 is no square there
    x, y = schnorrverify.lift_x(ec.GX)
    assert (x, y) == (ec.GX, ec.GY) and not y & 1


def test_reference_checks_the_signers_own_signatures_and_tweaks():
    for sk in (1, 3, 2**255 + 19, ec.N - 2):
        key, _ = ec.xonly_pubkey_create(sk)
        msg = bytes([sk % 251]) * 32
        sig = ec.sign_schnorr(sk, msg)
        assert schnorrverify.verify_schnorr(key, sig, msg)
        assert not schnorrverify.verify_schnorr(ec.xonly_pubkey_create(sk + 1)[0], sig, msg)
    for depth in (0, 1, 2):
        leaf = tapsigner.TapLeaf(7 + depth, tapsigner.leaf_script(b"\x11" * 32),
                                 [bytes([j + 1]) * 32 for j in range(depth)])
        root = tapref.tapleaf_hash(0xC0, leaf.script)
        assert root == leaf.leaf_hash
        for sib in leaf.siblings:
            root = tapref.tapbranch_hash(root, sib)
        tweak = tapref.taptweak_hash(leaf.internal, root)
        assert schnorrverify.tweak_add_check(leaf.output_key, leaf.parity, leaf.internal, tweak)
        assert not schnorrverify.tweak_add_check(leaf.output_key, leaf.parity ^ 1, leaf.internal, tweak)
        assert not schnorrverify.tweak_add_check(leaf.output_key, leaf.parity, leaf.internal,
                                                 tweak[:-1] + bytes([tweak[-1] ^ 1]))
        assert len(leaf.control()) == 33 + 32 * depth and leaf.control()[0] == 0xC0 | leaf.parity


def test_reference_agrees_with_the_program_where_both_speak():
    """The two digests against the program's own, on the generator's block:
    two implementations, one answer."""
    from bitcoinconsensus_tpu.core.sighash import PrecomputedTxData, SigVersion, bip341_sighash
    from bitcoinconsensus_tpu.core.tx import Tx, TxOut

    _, d = _build(12)
    t = next(i for i, rec in enumerate(d["txs"]) if len(rec["outs"]) == 7)
    record = d["txs"][t]
    theirs, ours = Tx.deserialize(record["raw"]), tapref.Spend(record["raw"], record["outs"])
    txdata = PrecomputedTxData(theirs, [TxOut(a, s) for a, s in record["outs"]], force=True)
    for i in range(7):
        for hash_type in (0, 1, 2, 3, 0x81, 0x82, 0x83):
            assert ours.sighash(i, hash_type, None) == bip341_sighash(
                theirs, i, hash_type, SigVersion.TAPROOT, txdata, False, b"")
            leaf = bytes([i + 1]) * 32
            assert ours.sighash(i, hash_type, leaf) == bip341_sighash(
                theirs, i, hash_type, SigVersion.TAPSCRIPT, txdata, False, b"", tapleaf_hash=leaf)
        assert ours.sighash(i, 4, None) is None and ours.sighash(i, 0x80, None) is None
    assert ours.sighash(1, 3, None) is None  # SIGHASH_SINGLE with no output beside the input


def test_what_the_reference_does_not_implement_raises():
    leaf = tapsigner.TapLeaf(5, b"\x76\x51", [])  # OP_DUP OP_1
    raw = (b"\x02\x00\x00\x00\x00\x01\x01" + b"\x22" * 32 + b"\x00\x00\x00\x00\x00\xff\xff\xff\xff"
           b"\x01\xe8\x03\x00\x00\x00\x00\x00\x00\x01\x51")
    def spend(witness):
        body = bytes([len(witness)]) + b"".join(bytes([len(w)]) + w for w in witness)
        return raw + body + b"\x00\x00\x00\x00"
    with pytest.raises(tapref.Unsupported, match="opcode 0x76"):
        tapref.verify_input(spend([b"\x01", leaf.script, leaf.control()]), 0, [(5000, leaf.spk)])
    with pytest.raises(tapref.Unsupported, match="annex"):
        tapref.verify_input(spend([b"\x01" * 64, b"\x50\x00"]), 0, [(5000, leaf.spk)])
    with pytest.raises(tapref.Unsupported, match="neither P2TR nor P2WPKH"):
        tapref.verify_input(spend([b"\x01"]), 0, [(5000, b"\x00\x20" + b"\x33" * 32)])
    # an OP_SUCCESSx anywhere in a leaf passes it unexecuted, as BIP 342 says
    ok = tapsigner.TapLeaf(5, b"\x76\x50", [])
    assert tapref.verify_input(spend([ok.script, ok.control()]), 0, [(5000, ok.spk)]).ok
    # and the sigop cost of a witness v1 spend is none
    assert sigopref.tx_sigop_cost(sigopref.parse_tx(spend([ok.script, ok.control()])), [(5000, ok.spk)]) == 0


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
    assert detail["sigop_cost"] == [9]
    assert detail["lanes_by_kind"] == detail["lanes_built"] == {"ecdsa": 9, "schnorr": 57, "tweak": 12}
    assert sum(detail["reference_sample_checks"].values()) >= 8  # 8 sampled inputs


@pytest.mark.parametrize("control", CONTROLS)
def test_broken_run_is_not_correct(control):
    assert _run(control, 43)["correct"] is False


def test_rehearse_py_passes_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "rehearse.py"), "--workload", CELL],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"correct": true' in out.stdout.splitlines()[-1]


def test_readers_read_a_connect_and_nothing_else():
    """Each new reader on a hand-made context, and on one from a program
    that does not feed the counters in a connect."""
    def samples(label, values):
        return {"samples": [{"labels": {label: k}, "value": v} for k, v in values.items()]}

    ctx = {"driver": {
        "kind": "connect", "n_inputs": 100, "walls_s": [0.5, 0.5],
        "counters_before": {
            "consensus_checks_total": samples("kind", {"ecdsa": 5, "schnorr": 5, "tweak": 0}),
            "consensus_taproot_hash_total": samples("what", {"sighash": 10, "leaf": 0, "branch": 0, "tweak": 0}),
        },
        "counters_after": {
            "consensus_checks_total": samples("kind", {"ecdsa": 35, "schnorr": 195, "tweak": 40}),
            "consensus_taproot_hash_total": samples("what", {"sighash": 200, "leaf": 40, "branch": 80, "tweak": 40}),
        },
    }, "trace": None}
    read = {n: run.load_reader(n) for n in (
        "schnorr_lane_share.connect", "tweak_lane_share.connect", "taphashes_per_input.connect")}
    assert read["schnorr_lane_share.connect"](ctx) == pytest.approx(100 * 190 / 260)
    assert read["tweak_lane_share.connect"](ctx) == pytest.approx(100 * 40 / 260)
    assert read["taphashes_per_input.connect"](ctx) == pytest.approx(350 / 200)
    # the parent's tree: `consensus_checks_total` is there and a connect never
    # bumps it; `consensus_taproot_hash_total` is not registered
    still = {"consensus_checks_total": samples("kind", {"ecdsa": 5})}
    old = {"driver": {**ctx["driver"], "counters_before": still, "counters_after": still}, "trace": None}
    for name, reader in read.items():
        assert reader(old) is None, name
        assert reader({"driver": {"kind": "served"}, "trace": None}) is None, name
