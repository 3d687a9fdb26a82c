"""Batched native surfaces: digests, verify_inputs, oracle publish, and
CHECKMULTISIG speculation.

These are the one-C-call-per-phase paths verify_batch runs a block through
(models/batch.py); each must agree bit-for-bit with its per-item twin:
- digest_checks / digest_streams vs models/sigcache.py `_key(_parts(...))`
  (a silent divergence would alias cache keys — and SigCache is a
  success-only SKIP cache, so aliasing admits unverified signatures);
- nat_verify_inputs vs nat_verify_input (verdicts, errors, per-input
  record slices);
- add_known_batch vs add_known (the deferral oracle);
- speculative multisig pairings: a 2-of-3 whose sigs belong to
  non-adjacent keys must resolve in ONE device dispatch (the pre-recorded
  reachable pairings answer the re-interpretation's oracle reads).
"""

import hashlib
import os

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_LIBCONSENSUS
from bitcoinconsensus_tpu.core.script import OP_CHECKMULTISIG, push_data
from bitcoinconsensus_tpu.core.sighash import SIGHASH_ALL, bip143_sighash
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier
from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.utils.hashes import hash160

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: first calls
]


def _sk(seed: str) -> int:
    return int.from_bytes(hashlib.sha256(seed.encode()).digest(), "big") % H.N


def _rand(n: int, seed: str) -> bytes:
    out = b""
    i = 0
    while len(out) < n:
        out += hashlib.sha256(f"{seed}/{i}".encode()).digest()
        i += 1
    return out[:n]


def _mixed_checks():
    return [
        SigCheck("ecdsa", (_rand(33, "pk"), _rand(71, "sig"), _rand(32, "m"))),
        SigCheck("ecdsa", (_rand(65, "pk2"), b"", _rand(32, "m2"))),  # empty part
        SigCheck("schnorr", (_rand(32, "xpk"), _rand(64, "s64"), _rand(32, "m3"))),
        SigCheck("tweak", (_rand(32, "q"), 0, _rand(32, "p"), _rand(32, "t"))),
        SigCheck("tweak", (_rand(32, "q"), 1, _rand(32, "p"), _rand(32, "t"))),
    ]


def test_digest_checks_matches_python_key():
    cache = SigCache()
    checks = _mixed_checks()
    native = cache.keys_for_checks(checks)
    python = [cache._key(cache._parts(c.kind, c.data)) for c in checks]
    assert native == python
    # parity is part of the key: the two tweak checks differ only in parity
    assert native[3] != native[4]


def test_digest_streams_matches_python_key():
    cache = ScriptExecutionCache()
    items = [
        ScriptExecutionCache._parts(_rand(32, "w"), 3, VERIFY_ALL_LIBCONSENSUS, _rand(32, "d")),
        (b"", b"x", b""),  # empty parts must still length-prefix
        (_rand(600, "big"),),
    ]
    assert native_bridge.digest_streams(cache._salt, items) == [
        cache._key(p) for p in items
    ]


def _p2wpkh_tx(seed: str, corrupt: bool = False):
    sk = _sk(seed)
    pub = H.pubkey_create(sk)
    spk = b"\x00\x14" + hash160(pub)
    amount = 50_000
    tx = Tx(2, [TxIn(OutPoint(_rand(32, seed), 0))], [TxOut(amount - 1000, b"\x51")], 0)
    code = b"\x76\xa9" + push_data(hash160(pub)) + b"\x88\xac"
    sighash = bip143_sighash(code, tx, 0, SIGHASH_ALL, amount)
    sig = H.sign_ecdsa(sk, sighash) + bytes([SIGHASH_ALL])
    if corrupt:
        sig = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
    tx.vin[0].witness = [sig, pub]
    return tx.serialize(), spk, amount


def test_verify_inputs_matches_single():
    """Batched C verify == per-input C verify: verdicts, errors, records."""
    raws = [_p2wpkh_tx(f"vi/{i}", corrupt=(i == 1)) for i in range(3)]
    ntxs = [native_bridge.NativeTx(r) for r, _, _ in raws]
    for t in ntxs:
        t.precompute()
    flags = VERIFY_ALL_LIBCONSENSUS

    batch_sess = native_bridge.NativeSession()
    ok, err, unk, recs = batch_sess.verify_inputs(
        ntxs,
        [0] * 3,
        [a for _, _, a in raws],
        [s for _, s, _ in raws],
        [flags] * 3,
        mode=native_bridge.NativeSession.MODE_DEFER,
    )
    for i, ntx in enumerate(ntxs):
        sess = native_bridge.NativeSession()
        ok1, err1, unk1 = sess.verify_input(
            ntx, 0, raws[i][2], raws[i][1], flags,
            mode=native_bridge.NativeSession.MODE_DEFER,
        )
        assert bool(ok[i]) == ok1
        assert int(err[i]) == err1
        assert int(unk[i]) == unk1
        assert recs[i] == sess.take_records()

    # out-of-range index inside the batched call: rejected, no crash
    ok, err, unk, recs = batch_sess.verify_inputs(
        ntxs[:1], [5], [raws[0][2]], [raws[0][1]], [flags],
        mode=native_bridge.NativeSession.MODE_DEFER,
    )
    assert not ok[0] and recs[0] == []


def test_add_known_batch_feeds_oracle():
    """Results published via the batched call must answer oracle reads
    exactly like per-item add_known: unknown drops to 0 and the verdict
    reflects the published result."""
    raw, spk, amount = _p2wpkh_tx("akb")
    ntx = native_bridge.NativeTx(raw)
    ntx.precompute()
    flags = VERIFY_ALL_LIBCONSENSUS
    sess = native_bridge.NativeSession()
    ok, err, unk = sess.verify_input(ntx, 0, amount, spk, flags)
    assert ok and unk == 1  # optimistic, one oracle miss
    (kind, data), = sess.take_records()
    for verdict in (True, False):
        s2 = native_bridge.NativeSession()
        s2.add_known_batch([(kind, data, verdict)])
        ok2, _, unk2 = s2.verify_input(ntx, 0, amount, spk, flags)
        assert unk2 == 0 and ok2 == verdict


def _misaligned_multisig_item(seed: str = "spec"):
    """P2WSH 2-of-3 signed by keys 0 and 2: the CHECKMULTISIG cursor must
    discover the (sig1, key2) pairing, which only oracle answers reveal."""
    sks = [_sk(f"{seed}/k{i}") for i in range(3)]
    pubs = [H.pubkey_create(sk) for sk in sks]
    wscript = (
        b"\x52" + b"".join(push_data(p) for p in pubs) + b"\x53"
        + bytes([OP_CHECKMULTISIG])
    )
    spk = b"\x00\x20" + hashlib.sha256(wscript).digest()
    amount = 90_000
    tx = Tx(2, [TxIn(OutPoint(_rand(32, seed), 0))], [TxOut(amount - 900, b"\x51")], 0)
    sighash = bip143_sighash(wscript, tx, 0, SIGHASH_ALL, amount)
    sigs = [H.sign_ecdsa(sks[i], sighash) + bytes([SIGHASH_ALL]) for i in (0, 2)]
    tx.vin[0].witness = [b""] + sigs + [wscript]
    return BatchItem(tx.serialize(), 0, VERIFY_ALL_LIBCONSENSUS, spk, amount)


def test_misaligned_multisig_single_dispatch():
    """Speculative pairings resolve a misaligned 2-of-3 with ONE device
    dispatch — no second host->device round-trip."""
    item = _misaligned_multisig_item()
    verifier = TpuSecpVerifier()
    calls = []
    orig = verifier.verify_checks
    orig_lanes = verifier.dispatch_lanes

    def counting(checks):
        calls.append(len(checks))
        return orig(checks)

    def counting_lanes(args, n):  # the index-mode driver's dispatch seam
        calls.append(n)
        return orig_lanes(args, n)

    verifier.verify_checks = counting
    verifier.dispatch_lanes = counting_lanes
    res = verify_batch(
        [item], verifier=verifier, sig_cache=SigCache(),
        script_cache=ScriptExecutionCache(),
    )
    assert res[0].ok, (res[0].error, res[0].script_error)
    assert len(calls) == 1, f"expected one dispatch, saw {calls}"
    # the one dispatch carried the reachable pairings: (s0,k0) (s0,k1)
    # (s1,k1) (s1,k2) — 4 unique checks
    assert calls[0] == 4


def test_misaligned_multisig_corrupt_sig_fails():
    """Same shape but an invalid second sig: NULLFAIL applies and the
    verdict is an exact script failure, still without extra dispatches."""
    item = _misaligned_multisig_item("spec-bad")
    raw = bytearray(item.spending_tx)
    # corrupt one byte inside the second witness signature
    tx = Tx.deserialize(bytes(raw))
    w = list(tx.vin[0].witness)
    w[2] = w[2][:10] + bytes([w[2][10] ^ 1]) + w[2][11:]
    tx.vin[0].witness = w
    item = BatchItem(
        tx.serialize(), 0, item.flags, item.spent_output_script, item.amount
    )
    res = verify_batch(
        [item], verifier=TpuSecpVerifier(), sig_cache=SigCache(),
        script_cache=ScriptExecutionCache(),
    )
    assert not res[0].ok
