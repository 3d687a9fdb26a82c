"""The block of m-of-n CHECKMULTISIG spends at the weight limit: what the
generator makes and asserts, the plain reference to a `ScriptError` against
walks written out by hand, the driver's rules one at a time, and the cell
end to end at rehearsal size, sound and under each control."""

import importlib
import os
import pickle
import subprocess
import sys

import pytest

import run
from benchmarks.drivers import connect_multisig
from benchmarks.generators import multisigblock
from benchmarks.harness import chipguard, msigref, sigopref
from benchmarks.harness.cell import CONTROLS
from benchmarks.harness.tracer import Tracer

CELL = "worst-block-multisig20.fanout"


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
        assert d["n_inputs"] == blk["inputs"] == len(d["coins"]) == 4
        assert len(d["txs"]) == blk["txs"] == 2
        assert all(len(t["outs"]) == blk["inputs_per_tx"] for t in d["txs"])
        assert d["sigop_cost"] == blk["sigop_cost"] == 4 * 20
        assert d["pairings"] == blk["pairings"] == 4 * 8 * 13 and d["walk_pairings"] == 4 * 20
        assert blk["weight"][0] <= d["weight"] <= blk["weight"][1]
        assert len({t["victim"] for t in d["twins"]}) == 3 and d["unseen_txs"] == []
        assert (d["bad_block"], d["victim"]) == (d["twins"][0]["block"], d["twins"][0]["victim"])
    assert abs(a["weight"] - b["weight"]) <= 4 * 8 * 4  # a DER signature is 70 to 72 bytes


def test_the_generator_refuses_figures_that_are_not_the_blocks():
    spec = run.load_spec(CELL, rehearsal=True)
    for key, wrong in (("sigop_cost", 79), ("pairings", 415), ("weight", [1, 2]), ("txs", 3)):
        config = {**spec["config"], "block": {**spec["config"]["block"], key: wrong}}
        with pytest.raises(ValueError):
            multisigblock.build(config, spec["traffic"], 1, 4.0)


@pytest.mark.parametrize("seed", [9, 2**31 + 9])
def test_each_input_is_an_8_of_20_and_each_twin_fails_for_its_victim(seed):
    _, d = _build(seed)
    keys = set()
    for t in d["txs"]:
        tx = sigopref.parse_tx(t["raw"])
        assert len(tx.vout) == 1 and len(tx.vout[0][1]) == 22  # one P2WPKH output
        for i, txin in enumerate(tx.vin):
            dummy, *sigs, script = txin.witness
            assert dummy == b"" and len(sigs) == 8 and all(s[-1] == 1 for s in sigs)
            m, pubs = sigopref.parse_bare_multisig(script)
            assert (m, len(pubs)) == (8, 20) and all(len(p) == 33 for p in pubs)
            keys.update(pubs)
            v = msigref.verify_input(tx, i, t["outs"])
            # twelve keys fail the last-pushed signature, then eight pairings hold
            assert (v.ok, v.error) == (True, "OK")
            assert v.tried == [(7, k) for k in range(19, 7, -1)] + [(s, s) for s in range(7, -1, -1)]
    assert len(keys) == 4 * 20
    tried = {}
    for twin in d["twins"]:
        index = twin["victim"] - d["tx_start"][twin["tx"]["index"]]
        tx = sigopref.parse_tx(twin["tx"]["raw"])
        for i in range(len(tx.vin)):
            v = msigref.verify_input(tx, i, twin["tx"]["outs"])
            assert (v.ok, v.error) == ((False, "EVAL_FALSE") if i == index else (True, "OK"))
            if i == index:
                tried[twin["name"]] = v.tried
    # the first-pushed signature is the walk's last: it meets key 1 alone
    assert tried["first-signature"][-1] == (0, 0) and len(tried["first-signature"]) == 20
    # position 4's signature is flipped: it fails key 5, and then five
    # signatures face four keys
    assert tried["middle-signature"][-1] == (4, 4) and len(tried["middle-signature"]) == 12 + 3 + 1
    # positions 3 and 4 swapped: position 4 now holds key 4's signature, which
    # fails key 5 (each is valid for a listed key, and the order is wrong)
    assert tried["swapped-signatures"] == tried["middle-signature"]


def test_full_size_is_at_the_weight_limit():
    spec, d = _build(2**31 + 77, rehearsal=False)
    assert (d["n_inputs"], len(d["txs"]), d["sigop_cost"]) == (2750, 110, 55000)
    assert (d["pairings"], d["walk_pairings"]) == (286000, 55000)
    assert 0.988 * 4_000_000 < d["weight"] < 4_000_000 and 3_500_000 < len(d["block"]) < 3_700_000
    assert spec["config"]["reduced"] == [] and spec["config"]["oracle_sample"] == 64
    # 34 full chunks of 8,191 real lanes and one of 7,506: one padded shape
    assert divmod(d["pairings"], 8191) == (34, 7506)


# -- the plain reference, to its ScriptError ------------------------------------------

def test_strict_der_is_bip_66s():
    good = bytes.fromhex("3044022012345678901234567890123456789012345678901234567890123456789012340220"
                         "7bcdef7890123456789012345678901234567890123456789012345678901234") + b"\x01"
    assert msigref.valid_der(good)
    assert not msigref.valid_der(good[:-1])                              # no hash-type byte: lengths off
    assert not msigref.valid_der(b"\x31" + good[1:])                     # not a compound
    assert not msigref.valid_der(good[:4] + b"\x92" + good[5:])          # r negative
    assert not msigref.valid_der(good[:3] + b"\x00" + good[4:])          # r of no length
    padded = good[:3] + b"\x21\x00" + good[4:]
    assert not msigref.valid_der(bytes([0x30, padded[1] + 1]) + padded[2:])  # r padded with a zero it does not need
    assert not msigref.valid_der(b"\x30" * 74)                           # too long


def test_what_ends_a_script_and_what_the_reference_does_not_implement():
    _, d = _build(21)
    record = d["txs"][0]
    tx, outs = sigopref.parse_tx(record["raw"]), record["outs"]

    def with_witness(witness):
        vin = [tx.vin[0]._replace(witness=witness)] + tx.vin[1:]
        return tx._replace(vin=vin)

    dummy, *sigs, script = tx.vin[0].witness
    v = msigref.verify_input(with_witness([b"\x01"] + sigs + [script]), 0, outs)
    assert (v.ok, v.error) == (False, "SIG_NULLDUMMY")
    v = msigref.verify_input(with_witness([dummy] + sigs + [script[:-1] + b"\xaf"]), 0, outs)
    assert (v.ok, v.error, v.tried) == (False, "WITNESS_PROGRAM_MISMATCH", [])
    broken = sigs[:-1] + [b"\x31" + sigs[-1][1:]]  # the walk's first signature: not DER
    v = msigref.verify_input(with_witness([dummy] + broken + [script]), 0, outs)
    assert (v.ok, v.error) == (False, "SIG_DER")
    empty = [b""] + sigs[1:]  # an empty signature passes DERSIG and verifies against nothing
    v = msigref.verify_input(with_witness([dummy] + empty + [script]), 0, outs)
    assert (v.ok, v.error) == (False, "EVAL_FALSE") and v.tried[-1] == (0, 0)
    assert msigref.verify_input(with_witness([]), 0, outs).error == "WITNESS_PROGRAM_WITNESS_EMPTY"
    for witness in ([dummy] + sigs[1:] + [script],                       # seven signatures for eight
                    [dummy] + sigs[:-1] + [sigs[-1][:-1] + b"\x02"] + [script]):  # SIGHASH_NONE
        with pytest.raises(msigref.Unsupported):
            msigref.verify_input(with_witness(witness), 0, outs)
    with pytest.raises(msigref.Unsupported):
        msigref.verify_input(tx, 0, [(outs[0][0], b"\x00\x14" + b"\x11" * 20)] + outs[1:])


def test_reference_agrees_with_the_oracle_on_every_twin():
    from benchmarks.harness import oracle
    from bitcoinconsensus_tpu.core.flags import height_to_flags
    from bitcoinconsensus_tpu.core.script_error import ScriptError

    _, d = _build(12)
    flags = height_to_flags(d["height"], extended=True)
    for twin in d["twins"]:
        index = twin["victim"] - d["tx_start"][twin["tx"]["index"]]
        ok, _error, script_error = oracle.oracle_verdict(twin["tx"]["raw"], index, twin["tx"]["outs"], flags)
        v = msigref.verify_input(twin["tx"]["raw"], index, twin["tx"]["outs"])
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


def test_a_sound_window_is_correct_and_reports_the_lanes(driven):
    out = driven.verify()
    assert out["correct"] is True and out["problems"] == []
    assert [t["name"] for t in out["corrupted_block"]["twins"]] == list(multisigblock.corruptions(8))
    assert all(t["program"] == t["oracle"] == t["reference"] == (False, "EVAL_FALSE")
               for t in out["corrupted_block"]["twins"])
    assert out["compared"]["reference"]["sig_cache_entries"] == [4 * 8]
    assert out["compared"]["reference"]["sample"]["pairings_tried"] == 2 * 20
    detail = driven.detail()
    assert detail["sigop_cost"] == [80] and detail["pairings"] == 416
    assert detail["spec_pairings_a_connect"] == 416
    assert detail["walk_pairings_a_connect"] in (80, None)  # None: a program without the counter
    assert detail["reference_sample_walk"] == {"inputs": 2, "pairings_tried": 40}
    assert detail["phase_ms_p50"]["backpressure"] > 0  # seven chunks, a queue four deep
    assert set(driven.end_to_end()) == {"connect_ms_p50", "inputs_per_s"}


def test_a_poisoned_cache_is_not_correct(driven, monkeypatch):
    monkeypatch.setattr(driven, "cached", driven.cached | {4 * 8 + 96})
    out = driven.verify()
    assert out["correct"] is False and "signature cache" in out["problems"][-1]


def test_another_sigop_cost_is_not_correct(driven, monkeypatch):
    monkeypatch.setattr(driven, "costs", {79})
    out = driven.verify()
    assert out["correct"] is False and "sigop_cost" in " ".join(out["problems"])


def test_a_sampled_walk_that_differs_is_not_correct(driven, monkeypatch):
    real = msigref.verify_input

    def shifted(tx, index, spent):
        v = real(tx, index, spent)
        return v._replace(ok=False, error="EVAL_FALSE")

    monkeypatch.setattr(connect_multisig.msigref, "verify_input", shifted)
    out = driven.verify()
    assert out["correct"] is False and "key walk" in " ".join(out["problems"])


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
