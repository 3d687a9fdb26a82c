"""verify_batch() must agree bit-for-bit with the per-input API.

Mirrors the reference's batch-vs-single seam obligations (SURVEY §4
implication (4)): same verdicts, same Error codes, same ScriptErrors —
across P2PKH / P2SH-P2WPKH / P2WSH-multisig (the crate's own end-to-end
vectors, src/lib.rs:215-277) and synthetic P2TR key-path and script-path
spends (the taproot capability the reference C ABI cannot reach, §3.2).
"""

import hashlib
import struct

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import api
from bitcoinconsensus_tpu.api import ConsensusError, Error
from bitcoinconsensus_tpu.core.flags import (
    VERIFY_ALL_EXTENDED,
    VERIFY_ALL_LIBCONSENSUS,
)
from bitcoinconsensus_tpu.core.script import OP_CHECKSIG, push_data
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.core.sighash import (
    SIGHASH_ALL,
    SIGHASH_DEFAULT,
    PrecomputedTxData,
    SigVersion,
    bip143_sighash,
    bip341_sighash,
)
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.models.batch import (
    BatchItem,
    verify_batch,
    verify_batch_stream,
)
from bitcoinconsensus_tpu.utils.hashes import hash160, tagged_hash

from test_api_verify import (
    P2PKH_SPENDING,
    P2PKH_SPENT,
    P2SH_P2WPKH_SPENDING,
    P2SH_P2WPKH_SPENT,
    P2WSH_SPENDING,
    P2WSH_SPENT,
)

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls


def _sk(seed: str) -> int:
    return int.from_bytes(hashlib.sha256(seed.encode()).digest(), "big") % H.N


def _prevout(seed: str) -> OutPoint:
    return OutPoint(hashlib.sha256(seed.encode()).digest(), 0)


def make_p2wpkh_spend(seed: str, amount: int = 50_000, corrupt: bool = False):
    """Synthetic P2WPKH funding + spend, signed via our own BIP143 sighash."""
    sk = _sk(seed)
    pub = H.pubkey_create(sk)
    spk = b"\x00\x14" + hash160(pub)
    tx = Tx(
        version=2,
        vin=[TxIn(_prevout(seed))],
        vout=[TxOut(amount - 1000, b"\x51")],
        locktime=0,
    )
    script_code = (
        b"\x76\xa9" + push_data(hash160(pub)) + b"\x88\xac"
    )  # DUP HASH160 <h> EQUALVERIFY CHECKSIG
    sighash = bip143_sighash(script_code, tx, 0, SIGHASH_ALL, amount)
    sig = H.sign_ecdsa(sk, sighash) + bytes([SIGHASH_ALL])
    if corrupt:
        sig = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
    tx.vin[0].witness = [sig, pub]
    return tx.serialize(), spk, amount


def make_p2tr_keypath_spend(seed: str, amount: int = 70_000, corrupt: bool = False):
    """Synthetic taproot key-path spend (BIP86-style tweak, no script tree)."""
    d = _sk(seed)
    px, parity = H.xonly_pubkey_create(d)
    d_even = d if parity == 0 else H.N - d
    t = int.from_bytes(tagged_hash("TapTweak", px), "big") % H.N
    out_sk = (d_even + t) % H.N
    qx, _ = H.xonly_pubkey_create(out_sk)
    spk = b"\x51\x20" + qx
    tx = Tx(version=2, vin=[TxIn(_prevout(seed))], vout=[TxOut(amount - 500, b"\x51")], locktime=0)
    txdata = PrecomputedTxData(tx, [TxOut(amount, spk)], force=True)
    sighash = bip341_sighash(tx, 0, SIGHASH_DEFAULT, SigVersion.TAPROOT, txdata, False, b"")
    sig = H.sign_schnorr(out_sk, sighash)
    if corrupt:
        sig = sig[:40] + bytes([sig[40] ^ 2]) + sig[41:]
    tx.vin[0].witness = [sig]
    return tx.serialize(), spk, amount


def make_p2tr_scriptpath_spend(seed: str, amount: int = 90_000, corrupt: bool = False):
    """Synthetic taproot script-path spend: single tapscript leaf
    `<xonly> OP_CHECKSIG`, empty merkle path."""
    internal = _sk(seed + "/internal")
    leaf_sk = _sk(seed + "/leaf")
    ix, _ = H.xonly_pubkey_create(internal)
    lx, _ = H.xonly_pubkey_create(leaf_sk)
    script = push_data(lx) + bytes([OP_CHECKSIG])
    from bitcoinconsensus_tpu.core.serialize import ser_string

    tapleaf = tagged_hash("TapLeaf", bytes([0xC0]) + ser_string(script))
    t = int.from_bytes(tagged_hash("TapTweak", ix + tapleaf), "big") % H.N
    base = H.lift_x(int.from_bytes(ix, "big"))
    Q = H.PointJ.from_affine(*base).add(H.G.mul(t)).to_affine()
    qx, qy = Q
    spk = b"\x51\x20" + qx.to_bytes(32, "big")
    control = bytes([0xC0 | (qy & 1)]) + ix
    tx = Tx(version=2, vin=[TxIn(_prevout(seed))], vout=[TxOut(amount - 500, b"\x51")], locktime=0)
    txdata = PrecomputedTxData(tx, [TxOut(amount, spk)], force=True)
    sighash = bip341_sighash(
        tx, 0, SIGHASH_DEFAULT, SigVersion.TAPSCRIPT, txdata, False, b"",
        tapleaf_hash=tapleaf,
    )
    sig = H.sign_schnorr(leaf_sk, sighash)
    if corrupt:
        sig = sig[:5] + bytes([sig[5] ^ 8]) + sig[6:]
    tx.vin[0].witness = [sig, script, control]
    return tx.serialize(), spk, amount


def _single_verdict(item: BatchItem):
    """Run the per-input API on one BatchItem -> (ok, Error, ScriptError)."""
    try:
        if item.spent_outputs is not None:
            api.verify_with_spent_outputs(
                item.spending_tx, item.input_index, item.spent_outputs, item.flags
            )
        else:
            api.verify_with_flags(
                item.spent_output_script,
                item.amount,
                item.spending_tx,
                item.input_index,
                item.flags,
            )
        return True, Error.ERR_OK, ScriptError.OK
    except ConsensusError as e:
        return False, e.code, e.script_error


def _legacy_item(spent_hex, amount, spending_hex, index=0, flags=VERIFY_ALL_LIBCONSENSUS):
    return BatchItem(
        spending_tx=bytes.fromhex(spending_hex),
        input_index=index,
        flags=flags,
        spent_output_script=bytes.fromhex(spent_hex),
        amount=amount,
    )


def _taproot_item(tx_bytes, spk, amount):
    return BatchItem(
        spending_tx=tx_bytes,
        input_index=0,
        flags=VERIFY_ALL_EXTENDED,
        spent_outputs=[(amount, spk)],
    )


def test_batch_matches_single_mixed():
    items = [
        _legacy_item(P2PKH_SPENT, 0, P2PKH_SPENDING),
        _legacy_item(P2SH_P2WPKH_SPENT, 1900000, P2SH_P2WPKH_SPENDING),
        _legacy_item(P2WSH_SPENT, 18393430, P2WSH_SPENDING),
        # failures: corrupted script, wrong amount, bad index, bad flags
        _legacy_item(P2PKH_SPENT[:8] + "00" + P2PKH_SPENT[10:], 0, P2PKH_SPENDING),
        _legacy_item(P2SH_P2WPKH_SPENT, 900000, P2SH_P2WPKH_SPENDING),
        _legacy_item(P2PKH_SPENT, 0, P2PKH_SPENDING, index=5),
        _legacy_item(P2PKH_SPENT, 0, P2PKH_SPENDING, flags=1 << 30),
    ]
    for seed in ("w1", "w2"):
        txb, spk, amt = make_p2wpkh_spend(seed)
        items.append(
            BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS, spent_output_script=spk, amount=amt)
        )
    txb, spk, amt = make_p2wpkh_spend("w3", corrupt=True)
    items.append(BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS, spent_output_script=spk, amount=amt))
    for seed, make, corrupt in (
        ("t1", make_p2tr_keypath_spend, False),
        ("t2", make_p2tr_keypath_spend, True),
        ("t3", make_p2tr_scriptpath_spend, False),
        ("t4", make_p2tr_scriptpath_spend, True),
    ):
        txb, spk, amt = make(seed, corrupt=corrupt)
        items.append(_taproot_item(txb, spk, amt))

    # Two mixed batches, not one: legacy + failures + a segwit and a
    # taproot spend (~10 curve checks, the 16-lane rung), then the other
    # segwit and taproot spends (~7, the 8-lane rung). One batch of all 14
    # would be the only 32-lane dispatch in the suite.
    first, second = items[:8] + items[10:11], items[8:10] + items[11:]
    items = first + second
    got = verify_batch(first) + verify_batch(second)
    for i, item in enumerate(items):
        ok, err, serr = _single_verdict(item)
        assert got[i].ok == ok, f"item {i}: ok {got[i].ok} != {ok}"
        assert got[i].error == err, f"item {i}: {got[i].error} != {err}"
        if not ok and err == Error.ERR_SCRIPT:
            assert got[i].script_error == serr, (
                f"item {i}: {got[i].script_error} != {serr}"
            )


def test_batch_empty():
    assert verify_batch([]) == []


def test_batch_stream_matches_per_batch_verify():
    """verify_batch_stream must yield, per input batch and in order,
    results identical to a sequential verify_batch — the pipelining is a
    latency optimization, never a semantic one. (Takes the index-mode
    overlap path with the native core, the sync fallback without; both
    must hold.)"""
    batches = []
    for seed, corrupt in (("s1", False), ("s2", True), ("s3", False)):
        txb, spk, amt = make_p2wpkh_spend(seed, corrupt=corrupt)
        item = BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                         spent_output_script=spk, amount=amt)
        batches.append([item, _legacy_item(P2PKH_SPENT, 0, P2PKH_SPENDING)])
    want = [verify_batch(list(b)) for b in batches]
    got = list(verify_batch_stream(iter(batches), depth=2))
    assert len(got) == len(want)
    for g, w in zip(got, want, strict=True):
        assert [(r.ok, r.error, r.script_error) for r in g] == [
            (r.ok, r.error, r.script_error) for r in w
        ]


def test_batch_transport_error_order_matches_single():
    """A doubly-invalid item (trailing bytes AND out-of-range index) must
    report ERR_TX_INDEX from batch and single alike — index before size,
    the reference's check order (bitcoinconsensus.cpp:89-92)."""
    txb, spk, amt = make_p2wpkh_spend("order")
    combos = [
        (txb + b"\x00", 5),   # both invalid -> ERR_TX_INDEX
        (txb + b"\x00", 0),   # size only -> ERR_TX_SIZE_MISMATCH
        (txb, 5),             # index only -> ERR_TX_INDEX
        (txb, -1),            # negative: unsigned nIn semantics, no wraparound
        (txb, 0),             # valid
    ]
    items = [
        BatchItem(t, i, VERIFY_ALL_LIBCONSENSUS,
                  spent_output_script=spk, amount=amt)
        for t, i in combos
    ]
    got = verify_batch(items)
    singles = [_single_verdict(it) for it in items]
    for i, (res, (ok, err, _serr)) in enumerate(zip(got, singles, strict=True)):
        assert (res.ok, res.error) == (ok, err), f"combo {i}"
    assert got[0].error == Error.ERR_TX_INDEX
    assert got[1].error == Error.ERR_TX_SIZE_MISMATCH
    assert got[2].error == Error.ERR_TX_INDEX
    assert got[3].error == Error.ERR_TX_INDEX
    assert got[4].ok


def test_batch_wrong_length_prevout_list():
    """A spent_outputs list that doesn't match the input count must be a
    clean ERR_TX_INDEX (never an OOB read in the native precompute)."""
    txb, spk, amt = make_p2wpkh_spend("prevlen")
    for outs in ([], [(amt, spk), (amt, spk)]):
        res = verify_batch(
            [BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS, spent_outputs=outs)]
        )
        assert res[0].error == Error.ERR_TX_INDEX, (len(outs), res[0])


def test_taproot_single_api_roundtrip():
    txb, spk, amt = make_p2tr_keypath_spend("roundtrip")
    api.verify_with_spent_outputs(txb, 0, [(amt, spk)])
    txb, spk, amt = make_p2tr_scriptpath_spend("roundtrip2")
    api.verify_with_spent_outputs(txb, 0, [(amt, spk)])
    txb, spk, amt = make_p2tr_keypath_spend("roundtrip3", corrupt=True)
    with pytest.raises(ConsensusError) as ei:
        api.verify_with_spent_outputs(txb, 0, [(amt, spk)])
    assert ei.value.script_error == ScriptError.SCHNORR_SIG


def test_multisig_subset_resolves_on_device(monkeypatch):
    """A 2-of-3 whose sigs belong to the LOWER keys: the optimistic
    CHECKMULTISIG cursor guesses the wrong pairing, and the corrected
    control flow must converge via oracle rounds of batched device
    dispatches — never host EC math (the 14ms/input trap this guards)."""
    from bitcoinconsensus_tpu.core import interpreter as I
    from bitcoinconsensus_tpu.models.sigcache import (
        ScriptExecutionCache,
        SigCache,
    )
    from bitcoinconsensus_tpu.utils.blockgen import build_spend_tx, make_funded_view

    _, funded = make_funded_view(3, kinds=("p2wsh_multisig",), seed="msdev")
    items = []
    for f in funded:
        tx = build_spend_tx([f])
        items.append(
            BatchItem(
                tx.serialize(),
                0,
                VERIFY_ALL_LIBCONSENSUS,
                spent_output_script=f.wallet.spk,
                amount=f.amount,
            )
        )

    def boom(*a, **k):  # the host-crypto fallback must stay cold
        raise AssertionError("host EC verify reached on the device path")

    monkeypatch.setattr(I.TransactionSignatureChecker, "verify_ecdsa", boom)
    monkeypatch.setattr(I.TransactionSignatureChecker, "verify_schnorr", boom)
    res = verify_batch(
        items, sig_cache=SigCache(), script_cache=ScriptExecutionCache()
    )
    assert all(r.ok for r in res)


def _p2wsh_multisig_item(m, n, sign_keys, seed, corrupt_first=False):
    """P2WSH m-of-n CHECKMULTISIG spend signed by `sign_keys` (ascending
    key indices — consensus requires sig order to follow key order)."""
    from bitcoinconsensus_tpu.core.script import OP_CHECKMULTISIG

    def _count(x: int) -> bytes:
        # OP_1..OP_16 encode 1..16; larger counts (<= 20 keys) need a
        # minimal CScriptNum push.
        return bytes([0x50 + x]) if x <= 16 else push_data(bytes([x]))

    sks = [_sk(f"{seed}/k{i}") for i in range(n)]
    pubs = [H.pubkey_create(sk) for sk in sks]
    wscript = (
        _count(m)
        + b"".join(push_data(p) for p in pubs)
        + _count(n)
        + bytes([OP_CHECKMULTISIG])
    )
    spk = b"\x00\x20" + hashlib.sha256(wscript).digest()
    amount = 80_000
    tx = Tx(2, [TxIn(_prevout(seed))], [TxOut(amount - 700, b"\x51")], 0)
    sighash = bip143_sighash(wscript, tx, 0, SIGHASH_ALL, amount)
    sigs = [
        H.sign_ecdsa(_sk(f"{seed}/k{i}"), sighash) + bytes([SIGHASH_ALL])
        for i in sign_keys
    ]
    if corrupt_first:
        sigs[0] = sigs[0][:12] + bytes([sigs[0][12] ^ 1]) + sigs[0][13:]
    tx.vin[0].witness = [b""] + sigs + [wscript]
    return BatchItem(tx.serialize(), 0, VERIFY_ALL_LIBCONSENSUS, spk, amount)


def test_adversarial_multisig_oracle_work_is_bounded():
    """VERDICT r2 weak #7: an adversarial batch of maximally-misaligned
    deep CHECKMULTISIGs must stay bounded — the speculative pairing
    pre-record answers every cursor-reachable oracle read from the FIRST
    dispatch, so the whole batch resolves in <= 2 device dispatches and
    <= 2 interpretation passes per input, with verdicts (and exact
    ScriptErrors for the failing lanes) bit-identical to the single API.
    The count is the driver's, so the 256-lane dispatch is answered by the
    host stand-in: the EC kernel is not compiled at that size."""
    from packed_stub import host_lane_verdicts, install_kernel

    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
    from bitcoinconsensus_tpu.models.sigcache import (
        ScriptExecutionCache,
        SigCache,
    )

    items = [
        # worst-case cursor walk: the only sig belongs to the LAST key
        _p2wsh_multisig_item(1, 20, [19], "adv1of20"),
        # deep m-of-n, sigs for the top half (m(n-m+1)=110 reachable pairs)
        _p2wsh_multisig_item(10, 20, list(range(10, 20)), "adv10of20"),
        # misaligned and INVALID: first sig corrupted -> NULLFAIL error
        _p2wsh_multisig_item(2, 3, [0, 2], "advbad", corrupt_first=True),
        # aligned control lane
        _p2wsh_multisig_item(2, 3, [0, 1], "advok"),
    ]
    verifier = install_kernel(
        TpuSecpVerifier(min_batch=8), lambda args, n: host_lane_verdicts(*args)
    )
    dispatches = []
    orig = verifier.verify_checks
    orig_lanes = verifier.dispatch_lanes

    def counting(checks):
        dispatches.append(len(checks))
        return orig(checks)

    def counting_lanes(args, n):  # the index-mode driver's dispatch seam
        dispatches.append(n)
        return orig_lanes(args, n)

    verifier.verify_checks = counting
    verifier.dispatch_lanes = counting_lanes
    res = verify_batch(
        items, verifier=verifier, sig_cache=SigCache(),
        script_cache=ScriptExecutionCache(),
    )
    for item, got in zip(items, res, strict=True):
        want_ok, want_err, want_serr = _single_verdict(item)
        assert got.ok == want_ok
        if not want_ok:
            assert (got.error, got.script_error) == (want_err, want_serr)
    assert res[0].ok and res[1].ok and not res[2].ok and res[3].ok
    assert len(dispatches) <= 2, f"oracle work unbounded: {dispatches}"


def test_fixpoint_round_cap_exact_fallback():
    """`run_idx_fixpoint` round cap: inputs that never reach an exact
    verdict fall to the host-exact oracle bit-identically, counted in
    `consensus_exact_fallback_total`. Driven with a stub session whose
    interpreter reports one unresolved oracle miss forever (the pathology
    the cap exists for: a cursor that never converges)."""
    import numpy as np

    from bitcoinconsensus_tpu.models.batch import (
        _EXACT_FALLBACK,
        run_idx_fixpoint,
    )

    class _StuckSession:
        def uniq_count(self):
            return 0  # no uniq growth: _resolve_uniq is a no-op

        def spec_pairings(self):
            return 0

        def call_walks(self, n):
            return np.zeros(n, dtype=np.int64)

        def sighashes(self):
            return 0, 0

        def sighash_work(self):
            return {"legacy": (0, 0), "bip143": (0, 0)}

        def sighash_templates(self):
            return {"built": 0, "served": 0, "resumed": 0}

        def stages(self):
            from bitcoinconsensus_tpu.native_bridge import NativeStages

            return NativeStages({}, {})

        def lane_kinds(self):
            return {"ecdsa": 0, "schnorr": 0, "tweak": 0}

        def taproot_hashes(self):
            return {"sighash": 0, "leaf": 0, "branch": 0, "tweak": 0}

    calls = {"rounds": 0, "fallback": []}
    live = [3, 5, 8, 13]

    def run_idx(pos):
        calls["rounds"] += 1
        n = len(pos)
        return (
            np.ones(n, dtype=bool),        # optimistic ok
            np.zeros(n, dtype=np.int32),   # err
            np.ones(n, dtype=np.int32),    # unk: one miss each, forever
            np.zeros(0, dtype=np.int32),   # rec_idx: nothing recorded
            np.zeros(1, dtype=np.int64),   # bounds
        )

    def exact_fallback(idx):
        calls["fallback"].append(idx)
        return (idx % 2 == 1, 0 if idx % 2 else 39)

    before = _EXACT_FALLBACK.value()
    ok, err = run_idx_fixpoint(
        _StuckSession(), None, None, live, run_idx, exact_fallback,
        max_rounds=3,
    )
    assert calls["rounds"] == 3  # the cap really bounded the loop
    assert sorted(calls["fallback"]) == live
    # verdict arrays indexed by input; rows outside `live` are not written
    assert len(ok) == len(err) == max(live) + 1
    assert {idx: (bool(ok[idx]), int(err[idx])) for idx in live} == {
        idx: (idx % 2 == 1, 0 if idx % 2 else 39) for idx in live
    }
    assert _EXACT_FALLBACK.value() == before + len(live)


def test_batch_all_script_cache_hits():
    """Replay edge: a batch whose every item hits the script-execution
    cache resolves without interpretation or dispatch, bit-identical to
    the first pass (the mempool->block skip, validation.cpp:1529-1536)."""
    from bitcoinconsensus_tpu.models.sigcache import (
        ScriptExecutionCache,
        SigCache,
    )

    items = []
    for seed in ("allhit-1", "allhit-2", "allhit-3"):
        txb, spk, amt = make_p2wpkh_spend(seed)
        items.append(
            BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                      spent_output_script=spk, amount=amt)
        )
    sig_cache = SigCache(cache_label="allhit-sig")
    script_cache = ScriptExecutionCache(cache_label="allhit-script")
    first = verify_batch(items, sig_cache=sig_cache,
                         script_cache=script_cache)
    assert [r.ok for r in first] == [True] * 3
    hits0 = script_cache.hits
    second = verify_batch(items, sig_cache=sig_cache,
                          script_cache=script_cache)
    assert [r.ok for r in second] == [True] * 3
    assert script_cache.hits == hits0 + len(items)  # every item a hit


# -- stream abandonment (generator close must settle the window) ------


class _RecordingVerifier:
    """Stub verifier: records sync_lanes calls, optionally raising."""

    def __init__(self, raise_on=()):
        self.calls = []
        self.raise_on = set(raise_on)

    def sync_lanes(self, pend, n):
        self.calls.append((pend, n))
        if pend in self.raise_on:
            raise RuntimeError(f"settle failed for {pend}")


def _stub_fixpoint(verifier):
    from bitcoinconsensus_tpu.models.batch import IdxFixpoint

    return IdxFixpoint(
        nsess=None,
        verifier=verifier,
        sig_cache=None,
        live=[0, 1],
        run_idx=lambda pos: None,
        exact_fallback=lambda idx: (False, 0),
    )


def test_idx_fixpoint_abandon_settles_inflight_tickets():
    """abandon() must sync every pending device ticket of the in-flight
    round (they hold buffers and backpressure slots) and clear the run,
    without executing the fixpoint."""
    v = _RecordingVerifier()
    run = _stub_fixpoint(v)
    run._in_flight = (
        ("interp",), ("grow", ("k1", "k2"), [("pend1", [1, 2]), ("pend2", [3])])
    )
    run.abandon()
    assert v.calls == [("pend1", 2), ("pend2", 1)]
    assert run._in_flight is None and len(run._pending) == 0


def test_idx_fixpoint_abandon_contains_settle_failures():
    """A ticket whose settle raises must not stop the remaining tickets
    from settling — abandonment is best-effort containment."""
    v = _RecordingVerifier(raise_on={"bad"})
    run = _stub_fixpoint(v)
    run._in_flight = (
        ("interp",), ("grow", (), [("bad", [1]), ("good", [2, 3])])
    )
    run.abandon()  # must not raise
    assert v.calls == [("bad", 1), ("good", 2)]
    assert run._in_flight is None and len(run._pending) == 0


def test_idx_fixpoint_abandon_without_inflight_round():
    run = _stub_fixpoint(_RecordingVerifier())
    run.abandon()
    assert len(run._pending) == 0 and run._in_flight is None


def test_abandon_stream_window_only_touches_idx_handles():
    from bitcoinconsensus_tpu.models.batch import _abandon_stream_window

    class _Run:
        abandoned = 0
        released = 0

        def abandon(self):
            _Run.abandoned += 1

        def release(self):  # the native session goes with the handle
            assert _Run.released < _Run.abandoned
            _Run.released += 1

    window = [
        ("idx", _Run(), [], []),
        ("done", ["results"]),       # already settled: nothing to do
        ("idx", None, [], []),       # begin() refused: no run object
        ("idx", _Run(), [], []),
    ]
    _abandon_stream_window(window)
    assert _Run.abandoned == 2 and _Run.released == 2
    assert window == []


def test_batch_stream_close_leaves_no_inflight_tickets():
    """Closing the stream generator mid-flight (the abandoned-consumer
    path) must settle every begun batch: the verifier's in-flight queue
    drains to depth 0 and keeps serving later batches."""
    from bitcoinconsensus_tpu.crypto.jax_backend import default_verifier

    batches = []
    for seed in ("close-1", "close-2", "close-3"):
        txb, spk, amt = make_p2wpkh_spend(seed)
        batches.append([BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                                  spent_output_script=spk, amount=amt)])
    gen = verify_batch_stream(iter(batches), depth=2)
    first = next(gen)  # window now holds begun-but-unfinished batches
    assert [r.ok for r in first] == [True]
    gen.close()  # GeneratorExit -> finally -> window abandonment
    assert default_verifier()._inflight.depth == 0
    # The pipeline is still healthy: a fresh batch verifies normally.
    again = verify_batch(batches[0])
    assert [r.ok for r in again] == [True]
