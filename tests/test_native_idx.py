"""Index-mode batch surface (nat_verify_inputs_idx + the uniq trio).

The session-resident protocol must be behaviorally identical to the wire
protocol it replaces (nat_verify_inputs + records drain + prep_pack +
digest_checks + add_known_batch):

- verdicts/errors/unknown-counts agree per input;
- input i's rec_idx slice names exactly the checks the wire path drains
  for input i (dedup aside);
- uniq_lanes == prep_pack of the same records, byte for byte;
- uniq_digests == SigCache keys of the same records;
- publish_uniq answers oracle reads exactly like add_known_batch;
- n_threads > 1 produces the SAME uniq order, rec_idx stream and
  verdicts as single-threaded (the shard merge is order-preserving);
- a session that served the index protocol can serve the wire protocol
  afterwards (index_mode resets — the ADVICE r4 protocol-mixing trap).
"""

import functools
import hashlib
import time
from dataclasses import dataclass

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core import flags as F
from bitcoinconsensus_tpu.core.flags import (
    VERIFY_ALL_EXTENDED,
    VERIFY_ALL_LIBCONSENSUS,
)
from bitcoinconsensus_tpu.core.interpreter import (
    TransactionSignatureChecker,
    verify_script,
)
from bitcoinconsensus_tpu.core.script import OP_CODESEPARATOR, OP_DROP, push_data
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.core.sighash import (
    SIGHASH_ALL,
    SIGHASH_ANYONECANPAY,
    SIGHASH_NONE,
    SIGHASH_SINGLE,
    PrecomputedTxData,
    bip143_sighash,
    legacy_sighash,
)
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.models.batch import DeferringSignatureChecker
from bitcoinconsensus_tpu.models.sigcache import SigCache
from bitcoinconsensus_tpu.utils.blockgen import build_spend_tx, make_funded_view
from bitcoinconsensus_tpu.utils.hashes import hash160

from test_worst_block import multisig_script

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: first calls
]


def _mixed_inputs(n=12, seed="idx", corrupt=()):
    """n inputs cycling p2wpkh / p2tr / p2wsh-2of3 as one spend tx; returns
    (ntxs, n_ins, amounts, spks, flags) ready for the batched calls."""
    kinds = ("p2wpkh", "p2tr", "p2wsh_multisig")
    _, funded = make_funded_view(n, kinds=kinds, seed=seed)
    tx = build_spend_tx(funded, fee=900)
    for i in corrupt:
        w = list(tx.vin[i].witness)
        j = 0 if len(w[0]) else 1
        w[j] = w[j][:6] + bytes([w[j][6] ^ 1]) + w[j][7:]
        tx.vin[i].witness = w
    raw = tx.serialize()
    spent = [(f.amount, f.wallet.spk) for f in funded]
    ntx = native_bridge.NativeTx(raw)
    ntx.set_spent_outputs(spent)
    ntxs = [ntx] * n
    n_ins = list(range(n))
    amounts = [f.amount for f in funded]
    spks = [f.wallet.spk for f in funded]
    flags = [VERIFY_ALL_EXTENDED] * n
    return ntxs, n_ins, amounts, spks, flags


def _wire_reference(args):
    """Run the same inputs through the wire protocol; returns
    (ok, err, unk, per-input record lists, session)."""
    sess = native_bridge.NativeSession()
    ok, err, unk, recs = sess.verify_inputs(
        *args, mode=native_bridge.NativeSession.MODE_DEFER
    )
    return ok, err, unk, recs, sess


def test_idx_matches_wire_protocol():
    args = _mixed_inputs()
    w_ok, w_err, w_unk, w_recs, w_sess = _wire_reference(args)
    w_spec = w_sess.take_spec()

    sess = native_bridge.NativeSession()
    ok, err, unk, rec_idx, bounds = sess.verify_inputs_idx(*args)
    assert np.array_equal(ok, w_ok)
    assert np.array_equal(err, w_err)
    assert np.array_equal(unk, w_unk)

    # Reconstruct per-input checks from uniq and compare to the wire drain.
    U = sess.uniq_count()
    all_idx = np.arange(U, dtype=np.int32)
    dig = sess.uniq_digests(b"salt!", all_idx)
    wire_digest = {}  # digest -> wire (kind, data)
    flat_wire = [r for recs in w_recs for r in recs] + w_spec
    wire_keys = native_bridge.digest_checks(b"salt!", flat_wire)
    for k, r in zip(wire_keys, flat_wire, strict=True):
        wire_digest[k] = r
    # every uniq entry is one of the wire-drained checks and vice versa
    uniq_keys = [dig[i].tobytes() for i in range(U)]
    assert set(uniq_keys) == set(wire_digest)

    # per-input slices name the same checks in the same order
    n = len(args[0])
    for i in range(n):
        mine = [uniq_keys[j] for j in rec_idx[int(bounds[i]) : int(bounds[i + 1])]]
        theirs = native_bridge.digest_checks(b"salt!", w_recs[i])
        assert mine == theirs, f"input {i}"

    # lanes parity: uniq lanes == prep_pack of the same records
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck

    checks = [SigCheck(k, d) for k, d in (wire_digest[k2] for k2 in uniq_keys)]
    size = max(8, U)
    ref = native_bridge.prep_pack(checks, size)
    mine = sess.uniq_lanes(all_idx, size)
    for a, b in zip(mine, ref, strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # digests parity vs the sigcache key stream
    cache = SigCache()
    assert [
        d.tobytes() for d in sess.uniq_digests(cache._salt, all_idx)
    ] == cache.keys_for_checks(checks)


def test_idx_threads_deterministic():
    args = _mixed_inputs(n=16, seed="idx-t")
    base = native_bridge.NativeSession()
    ok0, err0, unk0, ri0, b0 = base.verify_inputs_idx(*args, n_threads=1)
    d0 = [d.tobytes() for d in base.uniq_digests(b"s", np.arange(base.uniq_count(), dtype=np.int32))]
    for T in (2, 4, 7):
        s = native_bridge.NativeSession()
        ok, err, unk, ri, b = s.verify_inputs_idx(*args, n_threads=T)
        assert np.array_equal(ok, ok0) and np.array_equal(err, err0)
        assert np.array_equal(unk, unk0)
        assert np.array_equal(ri, ri0) and np.array_equal(b, b0)
        d = [d2.tobytes() for d2 in s.uniq_digests(b"s", np.arange(s.uniq_count(), dtype=np.int32))]
        assert d == d0


def test_publish_uniq_matches_add_known():
    args = _mixed_inputs(n=6, seed="idx-p", corrupt=(2,))
    sess = native_bridge.NativeSession()
    ok, err, unk, rec_idx, bounds = sess.verify_inputs_idx(*args)
    U = sess.uniq_count()
    # host-exact verdicts for every uniq entry, published back
    verdicts = np.asarray(
        [1 if sess.uniq_host_verify(i) else 0 for i in range(U)], dtype=np.int32
    )
    sess.publish_uniq(np.arange(U, dtype=np.int32), verdicts)
    ok2, err2, unk2, ri2, b2 = sess.verify_inputs_idx(*args)
    assert np.all(unk2 == 0)  # every oracle read now answered
    # corrupt input fails, the rest pass — matches the exact mode verdicts
    s_ex = native_bridge.NativeSession()
    ok_ex, err_ex, _, _ = s_ex.verify_inputs(
        *args, mode=native_bridge.NativeSession.MODE_EXACT
    )
    assert np.array_equal(ok2, ok_ex)
    assert np.array_equal(err2, err_ex)
    assert not ok2[2] and ok2[0] and ok2[1]


def test_idx_then_wire_protocol_mixing():
    """ADVICE r4: after an idx-mode call, the legacy wire path on the SAME
    session must drain real records again (index_mode resets)."""
    args = _mixed_inputs(n=3, seed="idx-mix")
    sess = native_bridge.NativeSession()
    sess.verify_inputs_idx(*args)
    assert sess.uniq_count() > 0
    ok, err, unk, recs = sess.verify_inputs(
        *args, mode=native_bridge.NativeSession.MODE_DEFER
    )
    for i in range(3):
        assert int(unk[i]) > 0
        assert len(recs[i]) == int(unk[i])  # records drained, not dropped

    # and single-input wire entry resets too
    sess2 = native_bridge.NativeSession()
    sess2.verify_inputs_idx(*args)
    ok1, err1, unk1 = sess2.verify_input(
        args[0][0], 0, args[2][0], args[3][0], args[4][0]
    )
    assert unk1 > 0 and len(sess2.take_records()) == unk1


def test_idx_driver_matches_wire_driver(monkeypatch):
    """verify_batch through the index-mode fast driver vs the legacy wire
    driver: identical BatchResults (ok/Error/ScriptError) on a mixed
    corpus with failures, transport errors and a misaligned multisig."""
    from bitcoinconsensus_tpu.core.flags import VERIFY_TAPROOT
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
    from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
    from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache

    kinds = ("p2wpkh", "p2tr", "p2wsh_multisig")
    # 6 inputs, 14 curve checks: one dispatch on the 16-lane rung.
    _, funded = make_funded_view(6, kinds=kinds, seed="idx-drv")
    tx = build_spend_tx(funded, fee=900)
    # corrupt input 4's witness signature
    w = list(tx.vin[4].witness)
    j = 0 if len(w[0]) else 1
    w[j] = w[j][:6] + bytes([w[j][6] ^ 1]) + w[j][7:]
    tx.vin[4].witness = w
    raw = tx.serialize()
    outs = [(f.amount, f.wallet.spk) for f in funded]
    items = [
        BatchItem(raw, i, VERIFY_ALL_EXTENDED, spent_outputs=outs)
        for i in range(6)
    ]
    # transport-error items ride along: bad index, truncated tx, bad flags
    items.append(BatchItem(raw, 99, VERIFY_ALL_EXTENDED, spent_outputs=outs))
    items.append(BatchItem(raw[:-4], 0, VERIFY_ALL_EXTENDED, spent_outputs=outs))
    items.append(
        BatchItem(raw, 0, VERIFY_TAPROOT, spent_output_script=outs[0][1], amount=outs[0][0])
    )

    def run(idx_on: bool):
        if idx_on:
            monkeypatch.delenv("BITCOINCONSENSUS_TPU_IDX", raising=False)
        else:
            monkeypatch.setenv("BITCOINCONSENSUS_TPU_IDX", "0")
        return verify_batch(
            items, verifier=TpuSecpVerifier(min_batch=8),
            sig_cache=SigCache(), script_cache=ScriptExecutionCache(),
        )

    fast = run(True)
    wire = run(False)
    assert [(r.ok, r.error, r.script_error) for r in fast] == [
        (r.ok, r.error, r.script_error) for r in wire
    ]
    assert [r.ok for r in fast[:6]] == [True] * 4 + [False, True]


def test_recidx_capacity_clamp():
    """nat_session_recidx_data copies at most `capacity` entries."""
    import ctypes

    args = _mixed_inputs(n=4, seed="idx-cap")
    sess = native_bridge.NativeSession()
    _, _, _, rec_idx, bounds = sess.verify_inputs_idx(*args)
    n_idx = int(bounds[-1])
    assert n_idx >= 2
    L = native_bridge.lib()
    buf = np.full(2, -1, dtype=np.int32)
    got = int(
        L.nat_session_recidx_data(
            sess._ptr, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), 2
        )
    )
    assert got == 2
    assert np.array_equal(buf, rec_idx[:2])


# --- The check store: verdicts by uniq index, every check once -----------

_SALT = b"idx-store"


def _uniq_digests(sess):
    idx = np.arange(sess.uniq_count(), dtype=np.int32)
    return [d.tobytes() for d in sess.uniq_digests(_SALT, idx)]


def _wire_round(args, known=()):
    """The executable spec of one round: a fresh wire-protocol session that
    knows exactly `known` interprets `args`. Returns (ok, err, unk,
    per-input record digests, digest -> (kind, data) of every check it
    drained, speculative pairings included: the bytes behind an index-mode
    session's uniq entries)."""
    ref = native_bridge.NativeSession()
    ref.add_known_batch(list(known))
    ok, err, unk, recs = ref.verify_inputs(
        *args, mode=native_bridge.NativeSession.MODE_DEFER
    )
    flat = [r for per in recs for r in per] + ref.take_spec()
    checks = dict(zip(native_bridge.digest_checks(_SALT, flat), flat, strict=True))
    return ok, err, unk, [native_bridge.digest_checks(_SALT, r) for r in recs], checks


@pytest.mark.parametrize("victim", ["ordinary", "speculative"])
def test_published_false_is_what_the_second_round_reads(victim):
    """One TRUE check published FALSE by index — a p2wpkh input's own
    check, or a multisig pairing that only speculation recorded — fails
    its input in the next round exactly as the wire protocol does when
    told the same verdicts with the bytes."""
    args = _mixed_inputs(n=6, seed="idx-false")
    sess = native_bridge.NativeSession()
    _, _, _, rec_idx, bounds = sess.verify_inputs_idx(*args)
    U = sess.uniq_count()
    verdicts = np.asarray(
        [1 if sess.uniq_host_verify(i) else 0 for i in range(U)], dtype=np.int32
    )
    if victim == "ordinary":
        flip, owner = int(rec_idx[int(bounds[0])]), 0  # input 0: p2wpkh
    else:
        spec_only = sorted(set(range(U)) - set(rec_idx.tolist()))
        flip = next(i for i in spec_only if verdicts[i])
        owner = 2  # the first p2wsh 2-of-3
    assert verdicts[flip]
    verdicts[flip] = 0
    sess.publish_uniq(np.arange(U, dtype=np.int32), verdicts)
    ok2, err2, unk2, _, _ = sess.verify_inputs_idx(*args)

    checks = _wire_round(args)[4]
    known = [
        (*checks[d], bool(v))
        for d, v in zip(_uniq_digests(sess), verdicts, strict=True)
    ]
    w_ok, w_err, w_unk, _, _ = _wire_round(args, known)
    assert np.array_equal(ok2, w_ok) and np.array_equal(err2, w_err)
    assert np.array_equal(unk2, w_unk) and not unk2.any()
    assert not ok2[owner] and int(ok2.sum()) == len(ok2) - 1
    assert sess.uniq_count() == U  # nothing was recorded twice


def test_unpublished_check_keeps_its_index():
    """A check recorded and not yet published is recorded again at the
    same uniq index by the next round, not duplicated."""
    args = _mixed_inputs(n=9, seed="idx-again")
    sess = native_bridge.NativeSession()
    first = sess.verify_inputs_idx(*args)
    U, digests = sess.uniq_count(), _uniq_digests(sess)
    half = np.arange(0, U, 2, dtype=np.int32)  # publish every other one TRUE
    sess.publish_uniq(half, np.ones(len(half), dtype=np.int32))
    again = sess.verify_inputs_idx(*args, n_threads=3)
    assert sess.uniq_count() == U and _uniq_digests(sess) == digests
    published = set(half.tolist())
    _, _, unk1, ri1, b1 = first
    _, _, unk2, ri2, b2 = again
    for i in range(len(args[0])):
        mine = ri2[int(b2[i]) : int(b2[i + 1])].tolist()
        # every miss of round 2 is an unpublished entry, at its old index
        assert not published & set(mine)
        assert len(mine) == int(unk2[i])
        before = ri1[int(b1[i]) : int(b1[i + 1])].tolist()
        assert [j for j in before if j not in published] == mine


def test_publish_exact_fallback_then_index_round():
    """The exact fallback between two index rounds (it switches the
    session to the wire protocol) loses no published verdict."""
    args = _mixed_inputs(n=6, seed="idx-exact", corrupt=(1,))
    sess = native_bridge.NativeSession()
    sess.verify_inputs_idx(*args)
    U = sess.uniq_count()
    verdicts = np.asarray(
        [1 if sess.uniq_host_verify(i) else 0 for i in range(U)], dtype=np.int32
    )
    sess.publish_uniq(np.arange(U, dtype=np.int32), verdicts)
    exact = [
        sess.verify_input(
            args[0][i], i, args[2][i], args[3][i], args[4][i],
            mode=native_bridge.NativeSession.MODE_EXACT,
        )
        for i in range(6)
    ]
    ok, err, unk, rec_idx, _ = sess.verify_inputs_idx(*args)
    assert not unk.any() and len(rec_idx) == 0 and sess.uniq_count() == U
    assert [bool(o) for o in ok] == [e[0] for e in exact]
    assert [int(e) for e in err] == [e[1] for e in exact]
    assert not ok[1] and int(ok.sum()) == 5
    # and a deferring wire call on the same session answers from them too
    w_ok, w_err, w_unk, w_recs = sess.verify_inputs(
        *args, mode=native_bridge.NativeSession.MODE_DEFER
    )
    assert np.array_equal(w_ok, ok) and np.array_equal(w_err, err)
    assert not w_unk.any() and not any(w_recs)


def _tripled(args):
    """The same inputs three times over: every check of the first copy is
    found again by the shards that interpret the second and third."""
    return tuple(list(a) * 3 for a in args)


@pytest.fixture(scope="module")
def tripled_single_thread():
    args = _tripled(_mixed_inputs(n=12, seed="idx-dup"))
    sess = native_bridge.NativeSession()
    out = sess.verify_inputs_idx(*args, n_threads=1)
    U = sess.uniq_count()
    idx = np.arange(U, dtype=np.int32)
    return args, out, _uniq_digests(sess), sess.uniq_lanes(idx, U)


@pytest.mark.parametrize("n_threads", [2, 4, 7])
def test_threads_identical_with_duplicates_across_shards(
    tripled_single_thread, n_threads
):
    args, (ok0, err0, unk0, ri0, b0), digests0, lanes0 = tripled_single_thread
    assert len(digests0) == len(set(digests0))  # deduped: 36 inputs, 12's checks
    sess = native_bridge.NativeSession()
    ok, err, unk, ri, b = sess.verify_inputs_idx(*args, n_threads=n_threads)
    assert np.array_equal(ok, ok0) and np.array_equal(err, err0)
    assert np.array_equal(unk, unk0)
    assert np.array_equal(ri, ri0) and np.array_equal(b, b0)
    assert _uniq_digests(sess) == digests0
    U = sess.uniq_count()
    lanes = sess.uniq_lanes(np.arange(U, dtype=np.int32), U)
    for mine, ref in zip(lanes, lanes0, strict=True):
        assert np.asarray(mine).tobytes() == np.asarray(ref).tobytes()


# -- the workers draw blocks of inputs from one cursor (PR 47) ------------------------

_QUEUE_INPUTS = 2999


def _fake_sig(tag: bytes, hash_type: int) -> bytes:
    """DER in shape only: with no flag set nothing reads a signature before
    the curve does, and a deferring round never asks the curve."""
    r, s = hashlib.sha256(b"r" + tag).digest()[:20], hashlib.sha256(b"s" + tag).digest()[:20]
    body = b"\x02\x14" + r + b"\x02\x14" + s
    return b"\x30" + bytes([len(body)]) + body + bytes([hash_type])


@functools.lru_cache(maxsize=None)
def _queue_block():
    """One legacy transaction of 2,999 inputs, two outputs: bare `<key>
    CHECKSIG` and bare 2-of-3 CHECKMULTISIG coins in turn under ALL, NONE and
    SINGLE, every key and signature distinct but one pair that every
    seventh input shares under SIGHASH_SINGLE past the outputs, whose digest
    is the number one: the same check, met by whichever workers draw them.
    Its digests cost by position (PR 47: the later the input, the fewer
    bytes), so no worker ends with the inputs it would have been dealt."""
    key = lambda tag: b"\x02" + hashlib.sha256(b"idx-queue/key/%s" % tag).digest()
    vin, spks = [], []
    for i in range(_QUEUE_INPUTS):
        hash_type = (SIGHASH_ALL, SIGHASH_NONE, SIGHASH_SINGLE)[i % 3]
        if i % 7 == 0:
            spks.append(push_data(key(b"shared")) + bytes([0xAC]))
            script_sig = push_data(_fake_sig(b"shared", SIGHASH_SINGLE))
        elif i % 2:
            spks.append(push_data(key(b"%d" % i)) + bytes([0xAC]))
            script_sig = push_data(_fake_sig(b"%d" % i, hash_type))
        else:
            spks.append(multisig_script(2, [key(b"%d/%d" % (i, k)) for k in range(3)]))
            script_sig = b"\x00" + b"".join(
                push_data(_fake_sig(b"%d/%d" % (i, k), hash_type)) for k in range(2))
        vin.append(TxIn(OutPoint(hashlib.sha256(b"idx-queue/%d" % i).digest(), i), script_sig,
                        0xFFFFFFF0))
    tx = Tx(version=1, vin=vin, vout=[TxOut(1, b"\x51"), TxOut(2, b"\x52")], locktime=0)
    return tx.serialize(), spks


def _queue_rounds(n, n_threads):
    """Two rounds of one session over the block's first `n` inputs, a verdict
    published for every check of round one in between (a bit of its salted
    digest: every run publishes the same): a round's verdict arrays, index
    stream, walk counts, and the uniq list in its order."""
    raw, spks = _queue_block()
    ntx = native_bridge.NativeTx(raw)
    ntx.precompute()
    args = ([ntx] * n, list(range(n)), [0] * n, spks[:n], [0] * n)
    sess, rounds = native_bridge.NativeSession(), []
    for _round in range(2):
        ok, err, unk, rec_idx, bounds = sess.verify_inputs_idx(*args, n_threads=n_threads)
        uniq = _uniq_digests(sess)
        rounds.append({"ok": ok.tolist(), "err": err.tolist(), "unk": unk.tolist(),
                       "rec_idx": rec_idx.tolist(), "rec_bounds": bounds.tolist(),
                       "call_walk": sess.call_walks(n).tolist(), "uniq": uniq,
                       "spec_pairings": sess.spec_pairings()})
        sess.publish_uniq(np.arange(len(uniq), dtype=np.int32),
                          np.array([d[0] & 1 for d in uniq], dtype=bool))
    return rounds, {stat: seconds for (call, stat), seconds in sess.stages().fans.items()
                    if call == "interpret"}


@pytest.fixture(scope="module")
def queue_one_thread():
    made = {}

    def get(n):
        if n not in made:
            made[n] = _queue_rounds(n, 1)
        return made[n]

    return get


# 26 is the fewest inputs thirteen workers take; none of the counts divides
# into the blocks drawn (of 1 to 46 inputs, by n and the width)
@pytest.mark.parametrize("n", [27, 131, _QUEUE_INPUTS])
@pytest.mark.parametrize("n_threads", [1, 2, 5, 13])
def test_workers_on_one_queue_leave_what_one_thread_leaves(queue_one_thread, n_threads, n):
    (first, second), seconds = queue_one_thread(n)
    assert seconds["sum"] == seconds["max"] > 0  # the caller's thread: one worker
    # the block has what the merge must carry: pairings pre-recorded ahead of
    # a CHECKMULTISIG's walk that no rec_idx entry names, and a check met again
    referenced = set(first["rec_idx"])
    assert 0 < len(first["uniq"]) - len(referenced) < first["spec_pairings"]
    assert len(first["rec_idx"]) > len(referenced)
    assert any(first["call_walk"]) and all(first["unk"]) and all(first["ok"])
    # round two re-interprets against the published verdicts: some inputs
    # fail now, walks go other ways, and nothing new is recorded
    assert 0 < sum(second["ok"]) < n and not any(second["unk"])
    assert second["uniq"] == first["uniq"] and second["call_walk"] != first["call_walk"]
    shared = []
    for _run in range(3):  # other timings, other owners of each block
        got, seconds = _queue_rounds(n, n_threads)
        for r, want in enumerate((first, second)):
            for what, value in want.items():
                assert got[r][what] == value, (r, what)
        assert 0 < seconds["max"] <= seconds["sum"]
        shared.append(seconds["max"] < seconds["sum"])
    if n == _QUEUE_INPUTS and n_threads > 1:
        assert any(shared)  # more than one worker drew inputs


# -- the native stage clock: a call's stages tile it, a fan-out accounts for itself (PR 50) --


def _stage_call(call, n_threads):
    """One call of `call` over the queue block's 2,999 inputs (the 6,427
    checks they record) at `n_threads`: the seconds around it on the
    caller's clock, what the session's stage clock rose by over it, and what
    it left (`_queue_rounds`' first round, the lanes, the digests)."""
    raw, spks = _queue_block()
    n = _QUEUE_INPUTS
    ntx = native_bridge.NativeTx(raw)
    ntx.precompute()
    sess = native_bridge.NativeSession()
    interpret = lambda: sess.verify_inputs_idx(
        [ntx] * n, list(range(n)), [0] * n, spks, [0] * n, n_threads=n_threads)
    if call == "interpret":
        run = interpret
    else:
        interpret()
        idx = np.arange(sess.uniq_count(), dtype=np.int32)
        run = {"lanes": lambda: sess.uniq_lanes(idx, len(idx), n_threads),
               "digests": lambda: sess.uniq_digests(b"salt", idx, n_threads)}[call]
    before = sess.stages()
    t0 = time.perf_counter()
    out = run()
    wall = time.perf_counter() - t0
    after = sess.stages()
    stages = {stage: (after.stages[c, stage][0] - before.stages[c, stage][0],
                      after.stages[c, stage][1] - before.stages[c, stage][1])
              for c, stage in after.stages if c == call}
    others = {k: v for k, v in after.stages.items() if k[0] != call}
    assert others == {k: v for k, v in before.stages.items() if k[0] != call}
    fan = {stat: after.fans[c, stat] - before.fans[c, stat]
           for c, stat in after.fans if c == call}
    if call == "interpret":
        left = (out[0].tolist(), out[1].tolist(), out[3].tolist(), _uniq_digests(sess))
    elif call == "lanes":
        left = [np.asarray(a).tobytes() for a in out]
    else:
        left = np.asarray(out).tobytes()
    return wall, stages, fan, left


_STAGES_OF = {"interpret": {"setup", "workers", "merge"}, "lanes": {"order", "shards"},
              "digests": {"shards"}}


@pytest.mark.parametrize("n_threads", [1, 4, 13])
@pytest.mark.parametrize("call", ["interpret", "lanes", "digests"])
def test_a_calls_stages_tile_it_and_its_fan_out_accounts_for_itself(call, n_threads):
    """`NativeSession.stages()` around one call of each of the session's
    three that fan out: every stage stamped once, their sum the call's
    duration as the caller's clock sees it (the same clock: never more, and
    all but the bridge's own work), the fan-out's six sums consistent, one
    worker where one thread was asked for, and the output the one-thread
    run's."""
    for _attempt in range(5):  # a loaded machine may take the caller off its core
        wall, stages, fan, left = _stage_call(call, n_threads)
        tiled = sum(seconds for seconds, _ in stages.values())
        if tiled >= 0.8 * wall:
            break
    assert set(stages) == _STAGES_OF[call]
    assert all(stamped == 1 and seconds >= 0 for seconds, stamped in stages.values()), stages
    assert 0.8 * wall <= tiled <= wall, (tiled, wall, stages)
    around = stages["workers" if call == "interpret" else "shards"][0]
    eps = 1e-12
    assert 0 < fan["wall"] <= around
    assert 0 < fan["max"] <= fan["wall"] and fan["max"] <= fan["sum"] <= fan["held"] + eps
    assert 0 <= fan["start_lag"] and 0 <= fan["tail"]
    assert fan["start_lag"] + fan["tail"] <= fan["wall"] + eps
    if n_threads == 1:
        assert fan["start_lag"] == 0 == fan["tail"]
        assert fan["sum"] == fan["max"] == fan["wall"] == fan["held"]
    else:
        width = round(fan["held"] / fan["wall"])
        assert width == min(n_threads, {"interpret": n_threads}.get(call, 6427 // 512))
        assert fan["start_lag"] > 0 and fan["held"] == pytest.approx(width * fan["wall"])
        assert left == _stage_call(call, 1)[3]


def test_release_frees_once_and_a_released_session_raises(monkeypatch):
    L = native_bridge.lib()
    freed = []
    real_free = L.nat_session_free
    monkeypatch.setattr(
        L, "nat_session_free", lambda p: (freed.append(p), real_free(p))
    )
    args = _mixed_inputs(n=3, seed="idx-rel")
    sess = native_bridge.NativeSession()
    sess.verify_inputs_idx(*args)
    sess.release()
    assert len(freed) == 1
    sess.release()
    sess.__del__()
    assert len(freed) == 1  # the backstop after an explicit release: harmless
    one = np.zeros(1, dtype=np.int32)
    for call in (
        sess.uniq_count,
        lambda: sess.verify_inputs_idx(*args),
        lambda: sess.publish_uniq(one, one),
        lambda: sess.uniq_lanes(one, 8),
        lambda: sess.uniq_digests(b"s", one),
        lambda: sess.uniq_host_verify(0),
        lambda: sess.add_known("ecdsa", (b"k", b"s", b"m"), True),
        lambda: sess.verify_input(args[0][0], 0, args[2][0], args[3][0], args[4][0]),
        sess.take_records,
    ):
        with pytest.raises(RuntimeError, match="after release"):
            call()
    assert len(freed) == 1
    # a session nobody released is still freed by __del__, once
    other = native_bridge.NativeSession()
    other.verify_inputs_idx(*args)
    del other
    assert len(freed) == 2


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_random_protocol_interleavings_match_a_dict_oracle(seed):
    """Random interleavings of index rounds, `publish_uniq`,
    `add_known_batch`, deferring wire calls and the exact fallback on ONE
    session, against a dict model of the oracle (check -> verdict, the last
    write wins). Each deferring call must give what a fresh wire-protocol
    session told exactly the model's entries gives: ok, err, unk, and the
    same misses an input. Verdicts are random, not the curve's, so the
    CHECKMULTISIG cursor walks every way it can."""
    import random

    rng = random.Random(seed)
    n = 12
    args = _mixed_inputs(n=n, seed=f"idx-rand-{seed}", corrupt=(rng.randrange(n),))
    exact_ref = native_bridge.NativeSession().verify_inputs(
        *args, mode=native_bridge.NativeSession.MODE_EXACT
    )
    sess = native_bridge.NativeSession()
    model = {}  # digest -> verdict
    universe = {}  # digest -> (kind, data), every check any round has seen

    def subset():
        pos = sorted(rng.sample(range(n), rng.randint(1, n)))
        return pos, tuple([a[i] for i in pos] for a in args)

    def known():
        return [(*universe[d], v) for d, v in model.items()]

    for _step in range(40):
        op = rng.choice(["idx", "idx", "wire", "publish", "add_known", "exact"])
        if op in ("idx", "wire"):
            pos, sub = subset()
            w_ok, w_err, w_unk, w_recs, seen = _wire_round(sub, known())
            universe.update(seen)
            if op == "idx":
                ok, err, unk, ri, b = sess.verify_inputs_idx(
                    *sub, n_threads=rng.choice([1, 2, 3])
                )
                digests = _uniq_digests(sess)
                assert len(digests) == len(set(digests))
                recs = [
                    [digests[j] for j in ri[int(b[i]) : int(b[i + 1])]]
                    for i in range(len(pos))
                ]
            else:
                ok, err, unk, raw = sess.verify_inputs(
                    *sub, mode=native_bridge.NativeSession.MODE_DEFER
                )
                recs = [native_bridge.digest_checks(_SALT, r) for r in raw]
            assert np.array_equal(ok, w_ok) and np.array_equal(err, w_err), op
            assert np.array_equal(unk, w_unk), op
            assert recs == w_recs, op
            assert not set(d for r in recs for d in r) & set(model)
        elif op == "publish":
            digests = _uniq_digests(sess)
            if not digests:
                continue
            idx = rng.sample(range(len(digests)), rng.randint(1, len(digests)))
            verdicts = [rng.random() < 0.6 for _ in idx]
            sess.publish_uniq(
                np.asarray(idx, dtype=np.int32), np.asarray(verdicts, dtype=np.int32)
            )
            for i, v in zip(idx, verdicts, strict=True):
                model[digests[i]] = v
        elif op == "add_known":
            if not universe:
                continue
            picks = rng.sample(sorted(universe), rng.randint(1, min(5, len(universe))))
            entries = [(*universe[d], rng.random() < 0.6) for d in picks]
            if rng.random() < 0.5:
                sess.add_known_batch(entries)
            else:
                for kind, data, v in entries:
                    sess.add_known(kind, data, v)
            for d, (_, _, v) in zip(picks, entries, strict=True):
                model[d] = v
        else:  # the exact fallback: answers from the curve, touches no verdict
            i = rng.randrange(n)
            okx, errx, _ = sess.verify_input(
                args[0][i], i, args[2][i], args[3][i], args[4][i],
                mode=native_bridge.NativeSession.MODE_EXACT,
            )
            assert bool(okx) == bool(exact_ref[0][i])
            assert int(errx) == int(exact_ref[1][i])
    assert universe and sess.uniq_count()


# -- one CHECKMULTISIG's record of its signatures ---------------------------
# native/eval.hpp MultisigSigs: the digest, the body and the encoding verdict
# of a signature are made once an op and shared by the speculation and the
# key walk. Every case runs the native core (exact; deferring on one thread
# and on four) against the executable spec (core/interpreter.py): same ok,
# same ScriptError, the same checks in the same order.

_MS_AMOUNT = 500_000
_MS_FLAGS = F.VERIFY_P2SH | F.VERIFY_WITNESS | F.VERIFY_NULLDUMMY
_MS_REPLICAS = 8  # verify_inputs_idx shards from 2 inputs a thread


@dataclass
class _MsCase:
    tx: Tx
    spk: bytes
    flags: int
    # Per CHECKMULTISIG executed, in order: (script code, is witness v0,
    # signatures in walk order, keys in walk order).
    ops: list
    # (computed, reused) a round of one input, where the case pins them.
    defer_counts: list = None
    exact_counts: tuple = None

    n_in = 1  # of two inputs and one output: SIGHASH_SINGLE has no output

    def spent(self):
        return [(_MS_AMOUNT, b"\x51"), (_MS_AMOUNT, self.spk)]

    def digest(self, script_code, v0, hash_type):
        if v0:
            return bip143_sighash(script_code, self.tx, self.n_in, hash_type, _MS_AMOUNT)
        return legacy_sighash(script_code, self.tx, self.n_in, hash_type)

    def uniq(self):
        """What the speculation pre-records: every signature against every
        key its cursor can reach, op by op, each check once."""
        out = []
        for script_code, v0, sigs, keys in self.ops:
            for s, sig in enumerate(sigs):
                if not sig:
                    continue
                msg = self.digest(script_code, v0, sig[-1])
                for key in keys[s : s + len(keys) - len(sigs) + 1]:
                    chk = ("ecdsa", (key, sig[:-1], msg))
                    if chk not in out:
                        out.append(chk)
        return out


def _ms_keys(tag, n):
    base = int.from_bytes(hashlib.sha256(tag.encode()).digest(), "big") % (H.N - n)
    sks = [base + 1 + j for j in range(n)]
    return sks, [H.pubkey_create(sk) for sk in sks]


def _ms_tx():
    ops = [OutPoint(hashlib.sha256(b"ms/op/%d" % i).digest(), i) for i in range(2)]
    return Tx(version=2, vin=[TxIn(op) for op in ops],
              vout=[TxOut(2 * _MS_AMOUNT - 1000, b"\x00\x14" + b"\x22" * 20)], locktime=0)


def _ms_der(r, s):
    def integer(v):
        b = v.to_bytes(32, "big").lstrip(b"\x00") or b"\x00"
        return b"\x02" + bytes([len(b) + (b[0] >> 7)]) + b"\x00" * (b[0] >> 7) + b
    body = integer(r) + integer(s)
    return b"\x30" + bytes([len(body)]) + body


def _ms_high_s(sig):
    r, s = H.parse_der_lax(sig)
    return _ms_der(r, H.N - s)


def _ms_non_der(sig):
    """One zero byte more than DER allows in front of R: the lax parser
    reads the same (r, s), the strict one refuses."""
    r_len = sig[3]
    return (b"\x30" + bytes([sig[1] + 1]) + b"\x02" + bytes([r_len + 1]) + b"\x00"
            + sig[4:])


def _ms_place(case_tx, kind, script, pushes):
    """Put `script` behind `kind` and the pushes (bottom first) in front of
    it; returns the scriptPubKey."""
    txin = case_tx.vin[_MsCase.n_in]
    if kind == "p2wsh":
        txin.witness = list(pushes) + [script]
        return b"\x00\x20" + hashlib.sha256(script).digest()
    txin.script_sig = b"".join(push_data(p) if p else b"\x00" for p in pushes)
    if kind == "p2sh":
        txin.script_sig += push_data(script)
        return b"\xa9\x14" + hash160(script) + b"\x87"
    return script


def _ms_hash_types(kind):
    """3-of-5 by the keys pushed first, third and fifth, each signature
    under another hash type: the walk pairs two of them wrongly first."""
    sks, pubs = _ms_keys("ms/types/" + kind, 5)
    script, tx, v0 = multisig_script(3, pubs), _ms_tx(), kind == "p2wsh"
    case = _MsCase(tx, b"", _MS_FLAGS | F.VERIFY_DERSIG, [])
    types = (SIGHASH_ALL, SIGHASH_NONE | SIGHASH_ANYONECANPAY, SIGHASH_SINGLE)
    sigs = [H.sign_ecdsa(sks[k], case.digest(script, v0, t)) + bytes([t])
            for k, t in zip((0, 2, 4), types)]
    case.spk = _ms_place(tx, kind, script, [b""] + sigs)
    case.ops = [(script, v0, sigs[::-1], pubs[::-1])]
    return case


def _ms_find_and_delete():
    """Legacy 2-of-3 whose scriptPubKey also pushes one of the two
    signatures: FindAndDelete takes that push out of what both sign."""
    sks, pubs = _ms_keys("ms/fad", 3)
    tx = _ms_tx()
    code = bytes([OP_DROP]) + multisig_script(2, pubs)
    case = _MsCase(tx, b"", _MS_FLAGS | F.VERIFY_DERSIG, [])
    sig_a = H.sign_ecdsa(sks[0], case.digest(code, False, SIGHASH_ALL)) + bytes([SIGHASH_ALL])
    sig_b = H.sign_ecdsa(sks[1], case.digest(code, False, SIGHASH_NONE)) + bytes([SIGHASH_NONE])
    case.spk = _ms_place(tx, "bare", push_data(sig_a) + code, [b"", sig_a, sig_b])
    case.ops = [(code, False, [sig_b, sig_a], pubs[::-1])]
    return case


def _ms_two_ops(kind):
    """`1 k0 k1 2 CHECKMULTISIG DROP CODESEPARATOR 1 k0 k1 2 CHECKMULTISIG`
    with one signature given to both: it signs the second op's script code,
    so the first op fails and the second passes only on a digest of its own."""
    sks, pubs = _ms_keys("ms/two/" + kind, 2)
    second = multisig_script(1, pubs)
    script = second + bytes([OP_DROP, OP_CODESEPARATOR]) + second
    tx, v0 = _ms_tx(), kind == "p2wsh"
    case = _MsCase(tx, b"", _MS_FLAGS | F.VERIFY_DERSIG, [])
    sig = H.sign_ecdsa(sks[0], case.digest(second, v0, SIGHASH_ALL)) + bytes([SIGHASH_ALL])
    case.spk = _ms_place(tx, kind, script, [b"", sig, b"", sig])
    case.ops = [(script, v0, [sig], pubs[::-1]), (second, v0, [sig], pubs[::-1])]
    return case


def _ms_encoding(fault, at, flag):
    """P2WSH 2-of-3 by the two keys pushed first. `at` 1: the faulty
    signature is the one the walk tries first; `at` 2: the other one, which
    the walk reaches once the first has taken a key."""
    sks, pubs = _ms_keys(f"ms/enc/{fault}/{at}/{flag}", 3)
    script, tx = multisig_script(2, pubs), _ms_tx()
    case = _MsCase(tx, b"", _MS_FLAGS | getattr(F, "VERIFY_" + flag), [])
    msg = case.digest(script, True, SIGHASH_ALL)
    wrong = hashlib.sha256(msg).digest()
    bodies = [H.sign_ecdsa(sk, msg) for sk in sks[:2]]
    victim = 2 - at  # push position: the walk starts from the last pushed
    if fault.startswith("wrong-"):
        bodies[victim] = H.sign_ecdsa(sks[victim], wrong)
    remake = _ms_high_s if fault.endswith("high-s") else _ms_non_der
    bodies[victim] = remake(bodies[victim])
    sigs = [b + bytes([SIGHASH_ALL]) for b in bodies]
    case.spk = _ms_place(tx, "p2wsh", script, [b""] + sigs)
    case.ops = [(script, True, sigs[::-1], pubs[::-1])]
    return case


def _ms_pin(position):
    """1-of-20 behind P2WSH, the worst block's input: the walk starts at
    the last-pushed key, so the first-pushed one costs all 20 pairings."""
    k = {"first-pushed": 0, "middle": 9, "last-pushed": 19}[position]
    sks, pubs = _ms_keys("ms/pin/" + position, 20)
    script, tx = multisig_script(1, pubs), _ms_tx()
    case = _MsCase(tx, b"", _MS_FLAGS | F.VERIFY_DERSIG, [])
    sig = H.sign_ecdsa(sks[k], case.digest(script, True, SIGHASH_ALL)) + bytes([SIGHASH_ALL])
    case.spk = _ms_place(tx, "p2wsh", script, [b"", sig])
    case.ops = [(script, True, [sig], pubs[::-1])]
    walk = 20 - k  # pairings of the exact walk
    # One digest a round; one read of it a pairing the walk makes: the one
    # optimistic pairing in round one, the whole walk in round two.
    # The last-pushed key's one guess holds, and there is no round two.
    case.defer_counts = [(1, 1)] + ([(1, walk)] if walk > 1 else [])
    case.exact_counts = (1, walk - 1)
    return case


_MS_CASES = {
    **{f"hash-types-{k}": functools.partial(_ms_hash_types, k)
       for k in ("bare", "p2sh", "p2wsh")},
    "find-and-delete": _ms_find_and_delete,
    **{f"two-ops-codeseparator-{k}": functools.partial(_ms_two_ops, k)
       for k in ("bare", "p2wsh")},
    **{f"{fault}-at-pairing-{'1' if at == 1 else 'k'}-{flag}":
       functools.partial(_ms_encoding, fault, at, flag)
       for fault in ("high-s", "non-der") for at in (1, 2)
       for flag in ("LOW_S", "DERSIG", "NULLFAIL")},
    **{f"wrong-high-s-at-pairing-{'1' if at == 1 else 'k'}-NULLFAIL":
       functools.partial(_ms_encoding, "wrong-high-s", at, "NULLFAIL") for at in (1, 2)},
    **{f"count-{p}": functools.partial(_ms_pin, p)
       for p in ("first-pushed", "middle", "last-pushed")},
}

_MS_ERRORS = {  # what the spec must say, so a case cannot pass by testing nothing
    "LOW_S": {"high-s": ScriptError.SIG_HIGH_S, "non-der": ScriptError.SIG_DER},
    "DERSIG": {"high-s": ScriptError.OK, "non-der": ScriptError.SIG_DER},
    "NULLFAIL": {"high-s": ScriptError.OK, "non-der": ScriptError.OK,
                 "wrong-high-s": ScriptError.SIG_NULLFAIL},
}


@functools.lru_cache(maxsize=None)
def _ms_case(name):
    return _MS_CASES[name]()


def _ms_spec(case, checker_type, **kw):
    txin = case.tx.vin[case.n_in]
    txdata = PrecomputedTxData(case.tx, [TxOut(a, s) for a, s in case.spent()])
    checker = checker_type(case.tx, case.n_in, _MS_AMOUNT, txdata, **kw)
    ok, err = verify_script(txin.script_sig, case.spk, txin.witness, case.flags, checker)
    return bool(ok), int(err), checker


@pytest.mark.parametrize("mode", ["exact", "defer-1-thread", "defer-4-threads"])
@pytest.mark.parametrize("name", list(_MS_CASES))
def test_multisig_signature_record_equals_the_spec(name, mode):
    case = _ms_case(name)
    want_ok, want_err, _ = _ms_spec(case, TransactionSignatureChecker)
    if "-at-pairing-" in name:
        fault, flag = name.split("-at-pairing-")[0], name.rsplit("-", 1)[1]
        assert want_err == _MS_ERRORS[flag][fault]
    else:
        assert want_ok
    ntx = native_bridge.NativeTx(case.tx.serialize())
    ntx.set_spent_outputs(case.spent())
    sess = native_bridge.NativeSession()

    if mode == "exact":
        ok, err, unk = sess.verify_input(ntx, case.n_in, _MS_AMOUNT, case.spk, case.flags,
                                         mode=native_bridge.NativeSession.MODE_EXACT)
        assert (bool(ok), err, unk) == (want_ok, want_err, 0)
        if case.exact_counts:
            assert sess.sighashes() == case.exact_counts
        return

    R, T = _MS_REPLICAS, int(mode.split("-")[1])
    args = ([ntx] * R, [case.n_in] * R, [_MS_AMOUNT] * R, [case.spk] * R, [case.flags] * R)
    expected = case.uniq()
    expected_keys = native_bridge.digest_checks(_SALT, expected)
    digests_a_round = sum(len({s[-1] for s in sigs if s}) for _c, _v, sigs, _k in case.ops)
    known, counts = {}, []
    for _round in range(4):
        before = sess.sighashes()
        ok, err, unk, rec_idx, bounds = sess.verify_inputs_idx(*args, n_threads=T)
        after = sess.sighashes()
        counts.append(tuple((a - b) // R for a, b in zip(after, before)))
        assert (after[0] - before[0]) == R * digests_a_round
        py_ok, py_err, chk = _ms_spec(case, DeferringSignatureChecker, known=known)
        assert set(zip(ok.tolist(), err.tolist(), unk.tolist())) == \
            {(int(py_ok), py_err, chk.unknown)}
        uniq = _uniq_digests(sess)
        assert uniq == expected_keys  # every reachable pairing, once, in order
        walked = native_bridge.digest_checks(_SALT, [(c.kind, c.data) for c in chk.recorded])
        for i in range(R):  # the walk's own misses: the spec's, in its order
            assert [uniq[j] for j in rec_idx[int(bounds[i]) : int(bounds[i + 1])]] == walked
        if chk.unknown == 0:
            break
        verdicts = np.array([sess.uniq_host_verify(i) for i in range(len(uniq))], dtype=bool)
        assert verdicts.tolist() == [H.verify_ecdsa(*d) for _k, d in expected]
        sess.publish_uniq(np.arange(len(uniq), dtype=np.int32), verdicts)
        known = dict(zip(expected, verdicts.tolist()))
        if all(known[(c.kind, c.data)] for c in chk.recorded):
            break  # every guess held: the driver accepts, no further round
    assert (py_ok, py_err) == (want_ok, want_err)
    if case.defer_counts:
        assert counts == case.defer_counts
