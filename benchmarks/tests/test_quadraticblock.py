"""The block of one legacy transaction of thousands of inputs: what the
generator makes and asserts, the plain legacy `SignatureHash` against
vectors built by hand, the driver's rules one at a time, the two readers,
and the cell end to end at rehearsal size, sound and under each control."""

import hashlib
import importlib
import os
import pickle
import struct
import subprocess
import sys

import pytest

import run
from benchmarks.drivers import connect_quadratic
from benchmarks.generators import megatxblock
from benchmarks.harness import chipguard, ec, sighashref, sigopref
from benchmarks.harness.cell import CONTROLS
from benchmarks.harness.tracer import Tracer

CELL = "worst-block-quadratic.sighash"
TWINS = ["signature-bit", "scripts-not-blanked", "hash-type-changed"]


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
        assert d["n_inputs"] == blk["inputs"] == len(d["coins"]) == 8
        assert len(d["txs"]) == blk["txs"] == 1 and d["tx_start"] == [0]
        assert (d["tx_bytes"], d["block_bytes"]) == (blk["tx_bytes"], blk["block_bytes"])
        assert len(d["txs"][0]["raw"]) == d["tx_bytes"] and len(d["block"]) == d["block_bytes"]
        assert d["sigop_cost"] == blk["sigop_cost"] == 4  # the one P2PKH output
        assert d["sighash_bytes"] == blk["sighash_bytes"] == 8 * 401
        assert d["n_inputs"] < spec["config"]["verifier"]["chunk"]  # one dispatch
        assert [t["name"] for t in d["twins"]] == TWINS
        assert len({t["victim"] for t in d["twins"]}) == 3 and d["unseen_txs"] == []
        assert all(len(t["block"]) == d["block_bytes"] for t in d["twins"])
        assert (d["bad_block"], d["victim"]) == (d["twins"][0]["block"], d["twins"][0]["victim"])


def test_the_generator_refuses_figures_that_are_not_the_blocks():
    spec = run.load_spec(CELL, rehearsal=True)
    for key, wrong in (("sigop_cost", 32), ("sighash_bytes", 3209), ("tx_bytes", 1479),
                       ("block_bytes", 1628), ("inputs", 70)):  # 70 checks: two dispatches of 64
        config = {**spec["config"], "block": {**spec["config"]["block"], key: wrong}}
        with pytest.raises(ValueError):
            megatxblock.build(config, spec["traffic"], 1, 4.0)


@pytest.mark.parametrize("seed", [9, 2**31 + 9])
def test_each_input_is_an_uncompressed_p2pkh_and_each_twin_fails_for_its_victim(seed):
    _, d = _build(seed)
    record = d["txs"][0]
    tx = sigopref.parse_tx(record["raw"])
    assert (tx.version, tx.locktime, len(tx.vout)) == (1, 0, 1) and len(tx.vout[0][1]) == 25
    keys = set()
    for i, txin in enumerate(tx.vin):
        (_, _, _, sig), (_, _, _, key) = sighashref._ops(txin.script_sig)
        assert len(sig) == (71, 72)[i % 2] and sig[-1] == 1 and len(key) == 65 and key[0] == 4
        assert len(txin.script_sig) + 41 in (179, 180) and not txin.witness
        keys.add(key)
        v = sighashref.verify_input(tx, i, record["outs"])
        assert v == (True, "OK", 401)  # 4 + 1 + 7 x 41 + 66 + 1 + 34 + 4 + 4
    assert len(keys) == 8
    for twin in d["twins"]:
        bad = sigopref.parse_tx(twin["tx"]["raw"])
        for i in range(len(bad.vin)):
            v = sighashref.verify_input(bad, i, twin["tx"]["outs"])
            assert (v.ok, v.error) == ((False, "EVAL_FALSE") if i == twin["victim"] else (True, "OK"))
    # SIGHASH_NONE commits to no output: its preimage is shorter by the output
    retyped = d["twins"][2]
    assert sighashref.verify_input(retyped["tx"]["raw"], retyped["victim"],
                                   retyped["tx"]["outs"]).preimage_bytes == 401 - 34


def test_full_size_is_the_megatransaction():
    spec, d = _build(2**31 + 77, rehearsal=False)
    blk = spec["config"]["block"]
    assert (d["n_inputs"], len(d["txs"]), d["sigop_cost"], d["height"]) == (5569, 1, 4, 364292)
    assert 996_000 <= d["tx_bytes"] == blk["tx_bytes"] <= 1_000_000
    assert len(d["block"]) == blk["block_bytes"] <= 1_000_000
    # 4 + 3 + 5,568 x 41 + 66 + 1 + 34 + 4 + 4 bytes an input, 5,569 times
    assert d["sighash_bytes"] == 5569 * 228404 == blk["sighash_bytes"] == 1_271_981_876
    assert spec["config"]["reduced"] == [] and spec["config"]["oracle_sample"] == 64
    assert d["n_inputs"] <= 8191  # one dispatch of the 8,192-lane shape


# -- the plain reference, against vectors built by hand ---------------------------------

KEY = ec.pubkey_create(7)
SPK = b"\x76\xa9\x14" + b"\x11" * 20 + b"\x88\xac"
TX = sigopref.Tx(2, [sigopref.TxIn(bytes([i]) * 32, i, b"\x51", 100 + i, []) for i in range(3)],
                 [(5000 + i, bytes([0x51 + i])) for i in range(2)], 7)


def _hand(index, hash_type, code=SPK):
    """The preimage written out field by field, with no loop shared with
    the reference: three inputs, two outputs."""
    def inp(i, script, seq):
        return bytes([i]) * 32 + struct.pack("<I", i) + script + struct.pack("<I", seq)
    own = bytes([len(code)]) + code
    base, acp = hash_type & 0x1F, hash_type & 0x80
    quiet = base in (2, 3)  # NONE and SINGLE zero the other inputs' sequences
    ins = [inp(i, own if i == index else b"\x00", 100 + i if i == index or not quiet else 0)
           for i in range(3)]
    if acp:
        ins = [ins[index]]
    outs = [struct.pack("<q", 5000) + b"\x01\x51", struct.pack("<q", 5001) + b"\x01\x52"]
    if base == 2:
        outs = []
    elif base == 3:
        outs = [struct.pack("<q", -1) + b"\x00"] * index + [outs[index]]
    return (struct.pack("<i", 2) + bytes([len(ins)]) + b"".join(ins) + bytes([len(outs)])
            + b"".join(outs) + struct.pack("<I", 7) + struct.pack("<i", hash_type))


@pytest.mark.parametrize("hash_type", [1, 2, 3, 0x81, 0x82, 0x83])
@pytest.mark.parametrize("index", [0, 1])
def test_the_six_hash_types_against_a_preimage_written_out_by_hand(index, hash_type):
    want = _hand(index, hash_type)
    assert sighashref.preimage(TX, index, SPK, hash_type) == want
    digest, size = sighashref.signature_hash(TX, index, SPK, hash_type)
    assert digest == hashlib.sha256(hashlib.sha256(want).digest()).digest() and size == len(want)


def test_sighash_single_past_the_outputs_is_the_number_one():
    for hash_type in (3, 0x83):
        assert sighashref.preimage(TX, 2, SPK, hash_type) is None
        assert sighashref.signature_hash(TX, 2, SPK, hash_type) == (b"\x01" + b"\x00" * 31, 0)
    assert sighashref.preimage(TX, 2, SPK, 1) is not None  # only SINGLE has the quirk


def test_codeseparators_leave_the_script_code_and_pushes_that_hold_one_do_not():
    code = b"\xab\x76\xab\x02\xab\xab\xac\xab"
    assert sighashref.serialize_script_code(code) == b"\x05\x76\x02\xab\xab\xac"
    # behind a push that runs past the end nothing is an operation any more
    assert sighashref.serialize_script_code(b"\xab\x05\xab\xab") == b"\x03\x05\xab\xab"
    assert sighashref.preimage(TX, 0, code, 1) == _hand(0, 1, code=b"\x76\x02\xab\xab\xac")


def test_find_and_delete_cuts_the_signatures_push_where_an_operation_starts():
    sig = b"\xaa\xbb"
    needle = b"\x02\xaa\xbb"
    fd = sighashref.find_and_delete
    assert fd(needle + b"\x75" + needle + needle + b"\xac", sig) == b"\x75\xac"
    assert fd(b"\x03" + needle + b"\xac", sig) == b"\x03" + needle + b"\xac"  # inside another push
    assert fd(b"\x4c\x02\xaa\xbb\xac", sig) == b"\x4c\x02\xaa\xbb\xac"        # another push of the same bytes
    assert fd(b"\xac" + needle + b"\x05\x01", sig) == b"\xac\x05\x01"          # before a push that runs past the end
    assert fd(b"\x00\x51\x00", b"") == b"\x51"                                  # an empty signature is OP_0
    assert sighashref.push(b"\x01" * 76)[:2] == b"\x4c\x4c" and sighashref.push(b"\x01" * 256)[:3] == b"\x4d\x00\x01"


def _verdict(spk, script_sig):
    """Input 0 of TX with `script_sig`, spending `spk`, through the reference."""
    tx = TX._replace(vin=[TX.vin[0]._replace(script_sig=script_sig)] + TX.vin[1:])
    return sighashref.verify_input(tx, 0, [(1000, spk), (1000, b"\x51"), (1000, b"\x51")])


def test_what_ends_a_script_and_what_the_reference_does_not_implement():
    digest, _ = sighashref.signature_hash(TX, 0, KEY.join([b"\x21", b"\xac"]), 1)
    sig = ec.sign_ecdsa(7, digest) + b"\x01"
    bare = b"\x21" + KEY + b"\xac"
    push = sighashref.push
    assert _verdict(bare, push(sig))[:2] == (True, "OK")
    assert _verdict(bare, push(sig[:-1] + b"\x02"))[:2] == (False, "EVAL_FALSE")
    assert _verdict(bare, push(b"\x31" + sig[1:]))[:2] == (False, "SIG_DER")
    assert _verdict(bare, b"\x00") == (False, "EVAL_FALSE", 0)  # empty: no digest
    assert _verdict(bare, b"")[:2] == (False, "INVALID_STACK_OPERATION")
    assert _verdict(bare, b"\x05\x01")[:2] == (False, "BAD_OPCODE")
    p2pkh = b"\x76\xa9\x14" + b"\x00" * 20 + b"\x88\xac"
    assert _verdict(p2pkh, push(sig) + push(KEY))[:2] == (False, "EQUALVERIFY")
    assert _verdict(b"\x21" + KEY + b"\xad\x51", push(sig))[:2] == (False, "CHECKSIGVERIFY")
    assert _verdict(b"\x01\x80", b"\x51")[:2] == (False, "EVAL_FALSE")  # negative zero
    for spk, script_sig in ((b"\xa9\x14" + b"\x22" * 20 + b"\x87", b"\x51"),  # P2SH
                            (b"\x00\x14" + b"\x22" * 20, b""),                  # a witness program
                            (b"\x51\x93", b"\x51"),                             # OP_ADD
                            (b"\x21" + KEY + b"\xac" + b"\x61" * 10_000, push(sig))):
        with pytest.raises(sighashref.Unsupported):
            _verdict(spk, script_sig)


@pytest.mark.parametrize("lead", [4, 6, 7])
def test_an_uncompressed_or_hybrid_key_verifies_where_its_point_is_the_keys(lead):
    x, y = ec.g_mul(7)
    key = bytes([lead]) + x.to_bytes(32, "big") + y.to_bytes(32, "big")
    lawful = lead == 4 or lead == 6 + (y & 1)
    assert (sighashref._compressed(key) == KEY) == lawful
    off_curve = key[:-1] + bytes([key[-1] ^ 1])
    assert sighashref._compressed(off_curve) is None and sighashref._compressed(key[:64]) is None


def test_reference_agrees_with_the_oracle_on_every_twin():
    from benchmarks.harness import oracle
    from bitcoinconsensus_tpu.core.flags import height_to_flags
    from bitcoinconsensus_tpu.core.script_error import ScriptError

    _, d = _build(12)
    flags = height_to_flags(d["height"], extended=True)
    for twin in d["twins"]:
        tx = twin["tx"]
        ok, _error, script_error = oracle.oracle_verdict(tx["raw"], twin["victim"], tx["outs"], flags)
        v = sighashref.verify_input(tx["raw"], twin["victim"], tx["outs"])
        assert (ok, ScriptError(script_error).name) == (v.ok, v.error) == (False, twin["error"])


# -- the driver's rules, one at a time ---------------------------------------------------

@pytest.fixture(scope="module")
def driven():
    """One sound window at rehearsal size; the tests break one thing each
    in what the driver recorded and ask it to judge again."""
    spec = run.load_spec(CELL, rehearsal=True)
    driver, _how = run.build_driver(spec["config"], spec["traffic"], 2**31 + 41, 2.0)
    driver.setup()
    driver.run_window(2.0, Tracer(False, "", 2.0))
    return driver


def test_a_sound_window_is_correct_and_reports_the_bytes(driven):
    out = driven.verify()
    assert out["correct"] is True and out["problems"] == []
    assert [t["name"] for t in out["corrupted_block"]["twins"]] == TWINS
    assert all(t["program"] == t["oracle"] == t["reference"] == (False, "EVAL_FALSE")
               for t in out["corrupted_block"]["twins"])
    assert out["compared"]["reference"]["sig_cache_entries"] == [8]
    assert out["compared"]["reference"]["sample"] == {
        "inputs": 2, "preimage_bytes": 802, "mismatches": 0, "first": []}
    detail = driven.detail()
    assert detail["sigop_cost"] == [4] and detail["reference_sighash_bytes"] == 3208
    assert detail["sighash_bytes_a_connect"] in (3208, None)  # None: a program without the counter
    assert detail["sighash_thread_s_a_connect"] is None or detail["sighash_thread_s_a_connect"] > 0
    assert detail["sha256_transform"] in ("sha-ni", "generic", None) and detail["host_cpus"] >= 1
    assert set(driven.end_to_end()) == {"connect_ms_p50", "inputs_per_s"}
    ctx = {"cell": CELL, "driver": driven.layer_context(), "trace": None}
    kb, rate = run.load_reader("sighash_kb_per_input.connect")(ctx), run.load_reader("sighash_mb_per_s.connect")(ctx)
    assert (kb, rate) == (None, None) or (kb == pytest.approx(0.401) and rate > 0)
    assert run.load_reader("sighashes_per_input.connect")(ctx) in (1.0, None)


@pytest.mark.parametrize("metric", ["sighash_kb_per_input.connect", "sighash_mb_per_s.connect"])
def test_the_readers_return_none_on_a_snapshot_without_the_counters(driven, metric):
    ctx = {"cell": CELL, "trace": None, "driver": {
        **driven.layer_context(),
        "counters_before": {"consensus_dispatch_total": {"samples": []}},
        "counters_after": {"consensus_dispatch_total": {"samples": []}}}}
    assert run.load_reader(metric)(ctx) is None
    assert run.load_reader(metric)({**ctx, "driver": {**ctx["driver"], "kind": "stream"}}) is None


def test_a_poisoned_cache_is_not_correct(driven, monkeypatch):
    monkeypatch.setattr(driven, "cached", driven.cached | {8 + 3})
    out = driven.verify()
    assert out["correct"] is False and "signature cache" in out["problems"][-1]


def test_another_sigop_cost_is_not_correct(driven, monkeypatch):
    monkeypatch.setattr(driven, "costs", {22276})
    out = driven.verify()
    assert out["correct"] is False and "sigop_cost" in " ".join(out["problems"])


def test_a_sampled_digest_that_differs_is_not_correct(driven, monkeypatch):
    real = sighashref.verify_input

    def shifted(tx, index, spent):
        return real(tx, index, spent)._replace(ok=False, error="EVAL_FALSE")

    monkeypatch.setattr(connect_quadratic.sighashref, "verify_input", shifted)
    out = driven.verify()
    assert out["correct"] is False and "SignatureHash" in " ".join(out["problems"])


# -- the cell, at rehearsal size -------------------------------------------------------

@pytest.mark.parametrize("control", CONTROLS)
def test_broken_run_is_not_correct(control):
    spec = run.load_spec(CELL, rehearsal=True)
    dev = dict(chipguard.device_info(), count=1)
    assert run.run_cell(spec, 43, 2.0, False, dev, control=control)["correct"] is False


def test_rehearse_py_passes_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "rehearse.py"), "--workload", CELL],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"correct": true' in out.stdout.splitlines()[-1]
