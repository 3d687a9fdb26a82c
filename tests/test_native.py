"""Native host core (native/libnat.so) vs the pure-Python oracle.

The C++ core must be bit-identical to `crypto/secp_host.py` (the
executable spec, itself differentially tested against the reference .so)
and to the Python lane packers in `crypto/jax_backend.py`. Covers the
verify algebras (valid / corrupted / structural garbage), lax-DER edge
vectors, GLV splitting (via packed lanes), hashing, and the batch prep
equality at production shapes.
"""

import hashlib
import os

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import native_bridge as NB
from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier
from bitcoinconsensus_tpu.utils.hashes import tagged_hash

pytestmark = pytest.mark.skipif(
    not NB.available(), reason="native library unavailable (no compiler?)"
)


def _sk(i: int) -> int:
    return (i * 2654435761 + 11) % (H.N - 1) + 1


def _msg(i: int) -> bytes:
    return hashlib.sha256(b"native-%d" % i).digest()


def test_single_verifies_match_oracle():
    ns = NB.NativeSecp
    for i in range(24):
        sk, msg = _sk(i), _msg(i)
        pub = H.pubkey_create(sk, compressed=bool(i % 2))
        sig = H.sign_ecdsa(sk, msg, grind_low_r=bool(i % 3))
        assert ns.verify_ecdsa(pub, sig, msg)
        # corrupted sig / wrong message / corrupted pubkey agree with oracle
        bad = sig[:6] + bytes([sig[6] ^ 1]) + sig[7:]
        assert ns.verify_ecdsa(pub, bad, msg) == H.verify_ecdsa(pub, bad, msg)
        assert not ns.verify_ecdsa(pub, sig, _msg(i + 1000))
        badpk = bytes([pub[0]]) + bytes([pub[1] ^ 1]) + pub[2:]
        assert ns.verify_ecdsa(badpk, sig, msg) == H.verify_ecdsa(badpk, sig, msg)

        xpk, par = H.xonly_pubkey_create(sk)
        ssig = H.sign_schnorr(sk, msg)
        assert ns.verify_schnorr(xpk, ssig, msg)
        bs = bytearray(ssig)
        bs[40] ^= 1
        assert not ns.verify_schnorr(xpk, bytes(bs), msg)
        bs = bytearray(ssig)
        bs[5] ^= 1  # corrupt r
        assert ns.verify_schnorr(xpk, bytes(bs), msg) == H.verify_schnorr(
            xpk, bytes(bs), msg
        )

        eff = sk if par == 0 else H.N - sk
        t = int.from_bytes(msg, "big") % (H.N - 1) + 1
        q, qpar = H.xonly_pubkey_create((eff + t) % H.N)
        t32 = t.to_bytes(32, "big")
        assert ns.tweak_add_check(q, qpar, xpk, t32)
        assert not ns.tweak_add_check(q, 1 - qpar, xpk, t32)
        assert ns.tweak_add_check(q, qpar, xpk, b"\xff" * 32) == \
            H.xonly_tweak_add_check(q, qpar, xpk, b"\xff" * 32)


def test_hybrid_and_garbage_pubkeys():
    ns = NB.NativeSecp
    sk, msg = _sk(99), _msg(99)
    sig = H.sign_ecdsa(sk, msg)
    x, y = H.G.mul(sk).to_affine()
    hybrid_ok = bytes([6 + (y & 1)]) + x.to_bytes(32, "big") + y.to_bytes(32, "big")
    hybrid_bad = bytes([7 - (y & 1)]) + x.to_bytes(32, "big") + y.to_bytes(32, "big")
    for pk in (hybrid_ok, hybrid_bad, b"", b"\x02", b"\x04" + b"\x00" * 64,
               b"\x02" + b"\xff" * 32):
        assert ns.verify_ecdsa(pk, sig, msg) == H.verify_ecdsa(pk, sig, msg), pk[:2]


def test_lax_der_edges_match_oracle():
    """Weird-but-parseable DER (the consensus-critical laxness) and
    structural failures must agree byte-for-byte with the oracle."""
    ns = NB.NativeSecp
    sk, msg = _sk(7), _msg(7)
    pub = H.pubkey_create(sk)
    sig = H.sign_ecdsa(sk, msg)
    r, s = H.parse_der_lax(sig)

    def der(r_bytes: bytes, s_bytes: bytes, seq=0x30, long_len=False) -> bytes:
        body = b"\x02" + bytes([len(r_bytes)]) + r_bytes
        body += b"\x02" + bytes([len(s_bytes)]) + s_bytes
        if long_len:
            # 0x81-prefixed length (lax parser skips), plus garbage tail
            return bytes([seq, 0x81, len(body)]) + body
        return bytes([seq, len(body)]) + body

    rb = r.to_bytes(32, "big")
    sb = s.to_bytes(32, "big")
    cases = [
        der(rb, sb),                                # minimal-ish re-encode
        der(b"\x00" * 5 + rb, sb),                  # non-minimal padding
        der(rb, b"\x00" + sb),                      # padded s
        der(rb, sb, long_len=True),                 # long-form length
        der(rb, sb) + b"\x00\x01",                  # trailing garbage
        der(b"\x00" * 40 + rb, sb),                 # >32 significant? no: zeros
        der(b"\x01" + rb, sb),                      # 33 significant bytes: overflow
        der(rb, (H.N + 1).to_bytes(33, "big")),     # s >= n: zeroed sig
        b"\x31" + der(rb, sb)[1:],                  # wrong seq tag
        der(rb, sb)[:10],                           # truncated
        b"\x30\x80",                                # dangling long length
        b"\x30\x00",
        b"",
    ]
    for c in cases:
        assert ns.verify_ecdsa(pub, c, msg) == H.verify_ecdsa(pub, c, msg), c.hex()


def test_hash_exports():
    L = NB.lib()
    for data in (b"", b"abc", b"x" * 1000, os.urandom(257)):
        out = np.zeros(32, np.uint8)
        arr = np.frombuffer(data, np.uint8) if data else np.zeros(1, np.uint8)
        L.nat_sha256(NB._u8p(arr), len(data), NB._u8p(out))
        assert out.tobytes() == hashlib.sha256(data).digest()
        L.nat_sha256d(NB._u8p(arr), len(data), NB._u8p(out))
        assert (
            out.tobytes() == hashlib.sha256(hashlib.sha256(data).digest()).digest()
        )
    tag = np.frombuffer(b"TapLeaf", np.uint8)
    data = os.urandom(77)
    arr = np.frombuffer(data, np.uint8)
    out = np.zeros(32, np.uint8)
    L.nat_tagged_hash(NB._u8p(tag), len(tag), NB._u8p(arr), len(data), NB._u8p(out))
    assert out.tobytes() == tagged_hash("TapLeaf", data)


def test_prep_pack_bit_identical_to_python():
    """The native lane prep must reproduce the Python packers bit-exactly
    across kinds, corruptions, and structural failures — including GLV
    splits, batched s^-1, has_t2, parity and the G_X invalid-lane fill."""
    import __graft_entry__ as ge

    checks = ge._example_checks(300)
    d = checks[9].data
    checks[9] = SigCheck("ecdsa", (b"\x05" + d[0][1:], d[1], d[2]))
    d = checks[10].data
    checks[10] = SigCheck("schnorr", (d[0][:31], d[1], d[2]))
    d = checks[3].data
    checks[3] = SigCheck("ecdsa", (d[0], b"\x30\x00", d[2]))
    d = checks[12].data
    checks[12] = SigCheck("ecdsa", (d[0], b"", d[2]))
    d = checks[5].data
    if checks[5].kind == "tweak":
        checks[5] = SigCheck("tweak", (d[0], d[1], d[2], b"\xff" * 32))
    d = checks[22].data
    checks[22] = SigCheck("schnorr", (b"\xff" * 32, d[1], d[2]))  # px >= p

    v = TpuSecpVerifier(min_batch=8)
    py = v._pack_lanes(v._prep_lanes(checks))
    nat = NB.prep_pack(checks, 512)
    names = ["fields", "want_odd", "parity", "has_t2", "neg1", "neg2", "valid"]
    for nm, a, b in zip(names, py, nat, strict=True):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, nm
        assert (a == b).all(), (nm, np.argwhere(a != b)[:5])


def test_randomized_differential_vs_oracle():
    """Random bytes through both ECDSA verifiers: agreement on arbitrary
    garbage, not only well-formed inputs."""
    rng = np.random.default_rng(1234)
    ns = NB.NativeSecp
    for i in range(60):
        publen = int(rng.integers(0, 70))
        siglen = int(rng.integers(0, 80))
        pub = rng.bytes(publen)
        sig = rng.bytes(siglen)
        msg = rng.bytes(32)
        assert ns.verify_ecdsa(pub, sig, msg) == H.verify_ecdsa(pub, sig, msg), i
        pk32, s64 = rng.bytes(32), rng.bytes(64)
        assert ns.verify_schnorr(pk32, s64, msg) == H.verify_schnorr(pk32, s64, msg)


def test_build_failure_keeps_the_compilers_stderr(tmp_path, monkeypatch):
    cxx = tmp_path / "cxx"
    cxx.write_text("#!/bin/sh\necho 'nat.cpp:1: fatal error: boom' >&2\nexit 3\n")
    cxx.chmod(0o755)
    monkeypatch.setenv("CXX", str(cxx))
    monkeypatch.setattr(NB, "_SO_PATH", str(tmp_path / "libnat.so"))
    reason = NB._build()
    assert "exited 3" in reason and "fatal error: boom" in reason
    monkeypatch.setenv("CXX", str(tmp_path / "no-such-compiler"))
    assert "did not run" in NB._build()
    assert NB.why_absent() is None  # the loaded core is unaffected


# --- Lane prep sharded over threads: the same lanes byte for byte ---------
#
# `NativeSession.uniq_lanes` / `uniq_digests` cut their entries into
# contiguous shards, one worker a shard, from 1,024 entries up. The session
# below holds 7,800 crafted checks, one an input in input order, so that
# invalid lanes sit on both sides of every shard boundary any case below
# cuts and two stretches hold no ECDSA lane (a shard without an inversion
# chain). A check reaches a session only through the interpreter, which
# always hands over a 32-byte sighash: a short message cannot be a uniq
# entry (`test_prep_pack_bit_identical_to_python` covers it on the wire
# twin, which stays serial).

_PREP_N = 7800
_PREP_SIZES = (0, 1, 255, 1023, 1024, _PREP_N)
_PREP_THREADS = (1, 2, 3, 8, 13)
_NO_ECDSA = (range(512, 1024), range(3000, 3600))  # shard 1 of 2, 5 of 13


def _boundary_lanes():
    """Both lanes at every cut any (n, n_threads) case makes, and each
    case's first and last lane."""
    out = set()
    for n in _PREP_SIZES:
        for t in _PREP_THREADS:
            shards = NB.prep_shards(n, t)
            for k in range(shards + 1):
                cut = n * k // shards
                out.update(i for i in (cut - 1, cut) if 0 <= i < n)
    return out


def _der(r: int, s: int) -> bytes:
    body = H._der_encode_int(r) + H._der_encode_int(s)
    return b"\x30" + bytes([len(body)]) + body


@pytest.fixture(scope="module")
def prep_session():
    """(session, indices 0..7799, the kind of every lane)."""
    import random

    from bitcoinconsensus_tpu.core.flags import (
        VERIFY_P2SH,
        VERIFY_TAPROOT,
        VERIFY_WITNESS,
    )
    from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut

    rng = random.Random(30)
    edge = _boundary_lanes()
    scalar = lambda: rng.randrange(1, H.N)  # noqa: E731
    x32 = lambda: rng.randrange(1, H.P).to_bytes(32, "big")  # noqa: E731
    over = b"\xff" * 32  # >= p and >= n
    bad = {"ecdsa": 0, "schnorr": 0, "tweak": 0}
    ntxs, spks, kinds = [], [], []
    for i in range(_PREP_N):
        kind = (("ecdsa",) * 10 + ("schnorr", "schnorr", "tweak"))[i % 13]
        if any(i in r for r in _NO_ECDSA):
            kind = "schnorr" if i % 2 else "tweak"
        which = -1
        if i in edge:
            which, bad[kind] = bad[kind], bad[kind] + 1
        script_sig, witness = b"", []
        if kind == "ecdsa":
            pub, r, s = b"\x02" + x32(), scalar(), scalar() % (H.N // 2) + 1
            if i % 97 == 5:
                s = H.N - s  # high s: normalized, a valid lane
            if i % 101 == 7:
                r = rng.randrange(1, 1 << 120)  # r + n < p: has_t2
            sig = _der(r, s)
            if which >= 0:
                pub, sig = [
                    (b"\x03" + over, sig),  # x >= p
                    (pub, _der(r, 0)),
                    (pub, _der(0, s)),
                    (b"\x04" + x32() + x32(), sig),  # not on the curve
                    (pub, b"\x30\x00"),  # no DER integers
                    (pub, _der(H.P - H.N + i, H.N - s)),  # r >= p - n, high s
                ][which % 6]
            spk = bytes([len(pub)]) + pub + b"\xac"  # <pub> CHECKSIG
            sig += b"\x01"
            script_sig = bytes([len(sig)]) + sig
        elif kind == "schnorr":
            q, sig = x32(), x32() + scalar().to_bytes(32, "big")
            if which >= 0:
                q, sig = [
                    (over, sig),  # px >= p
                    (q, over + sig[32:]),  # r >= p
                    (q, sig[:32] + over),  # s >= n
                ][which % 3]
            spk, witness = b"\x51\x20" + q, [sig]
        else:
            internal = over if which >= 0 else x32()
            spk = b"\x51\x20" + x32()
            witness = [b"\x51", bytes([0xC0 | (i & 1)]) + internal]  # OP_1
        prevout = OutPoint(i.to_bytes(32, "little"), 0)
        tx = Tx(2, [TxIn(prevout, script_sig, witness=witness)],
                [TxOut(1000, b"\x51")], 0)
        ntx = NB.NativeTx(tx.serialize())
        ntx.set_spent_outputs([(5000, spk)])
        ntxs.append(ntx)
        spks.append(spk)
        kinds.append(kind)
    assert (NB.prep_shards(1024, 8), NB.prep_shards(_PREP_N, 13)) == (2, 13)
    # every invalid variant is on some edge
    assert bad["ecdsa"] >= 6 and bad["schnorr"] >= 3 and bad["tweak"] >= 1
    sess = NB.NativeSession()
    flags = VERIFY_P2SH | VERIFY_WITNESS | VERIFY_TAPROOT  # lax DER, any s
    ok, _err, unk, rec_idx, _ = sess.verify_inputs_idx(
        ntxs, [0] * _PREP_N, [5000] * _PREP_N, spks, [flags] * _PREP_N,
        n_threads=1,
    )
    # one deferred check an input, recorded in input order
    assert ok.all() and (unk == 1).all() and sess.uniq_count() == _PREP_N
    assert np.array_equal(rec_idx, np.arange(_PREP_N))
    return sess, np.arange(_PREP_N, dtype=np.int32), kinds


def _lane_bytes(lanes):
    return [np.ascontiguousarray(a).tobytes() for a in lanes]


@pytest.mark.parametrize("n", _PREP_SIZES)
@pytest.mark.parametrize("n_threads", _PREP_THREADS)
def test_sharded_prep_is_the_serial_prep_byte_for_byte(prep_session, n_threads, n):
    sess, idx, kinds = prep_session
    idx, size = idx[:n], n + 7  # the padding lanes stay as allocated
    serial = sess.uniq_lanes(idx, size, 1)
    digests = sess.uniq_digests(b"prep-salt", idx, 1)
    if n_threads == 1:
        # the reference itself, against every lane prepped alone (its own
        # inversion, no batch)
        alone = [sess.uniq_lanes(idx[j : j + 1], 1) for j in range(n)]
        pad = sess.uniq_lanes(idx[:0], size - n)
        for col, whole in enumerate(serial):
            parts = [a[col] for a in alone] + [pad[col]]
            assert np.concatenate(parts).tobytes() == np.asarray(whole).tobytes()
        valid = serial[6][:n]
        if n == _PREP_N:
            ecdsa = np.asarray([k == "ecdsa" for k in kinds])
            edge = sorted(_boundary_lanes())
            ok_edges = int(valid[edge].sum())  # the "r >= p - n, high s" ones
            assert 0 < ok_edges <= len(edge) // 6
            assert valid.sum() == n - len(edge) + ok_edges
            assert serial[3][:n][ecdsa & valid].any()  # has_t2 lanes exist
        return
    assert _lane_bytes(sess.uniq_lanes(idx, size, n_threads)) == _lane_bytes(serial)
    sharded = sess.uniq_digests(b"prep-salt", idx, n_threads)
    assert sharded.tobytes() == digests.tobytes() and sharded.shape == (n, 32)


class _NoDevice:
    """What `_dispatch_uniq` needs of a verifier, and no device."""

    lane_capacity = 8192

    def __init__(self):
        from bitcoinconsensus_tpu.utils.profiling import Phases

        self.phases = Phases()
        self.launched = []

    def pad(self, n):
        return max(8, n)

    def dispatch_lanes(self, lanes, n):
        self.launched.append(n)


class _FirstEntries:
    """A session as the round sees it that discovered its first n checks."""

    def __init__(self, sess, n):
        self.sess, self.n = sess, n

    def uniq_count(self):
        return self.n

    def __getattr__(self, name):
        return getattr(self.sess, name)


@pytest.mark.parametrize(
    "n,threads,mode",
    [(13, "13", "serial"), (393, "13", "serial"), (1023, "13", "serial"),
     (1024, "13", "sharded"), (_PREP_N, "13", "sharded"),
     (_PREP_N, "1", "serial")],
)
def test_prep_counter_says_which_path_ran(prep_session, monkeypatch, n, threads, mode):
    """Under 1,024 lanes (a warm connect, a served batch) or on one thread
    no worker is made: the counter rises under `serial` alone."""
    from bitcoinconsensus_tpu.models import batch
    from bitcoinconsensus_tpu.models.sigcache import SigCache

    sess = _FirstEntries(prep_session[0], n)
    monkeypatch.setenv("BITCOINCONSENSUS_TPU_THREADS", threads)
    assert (NB.prep_shards(n, int(threads)) > 1) == (mode == "sharded")
    before = {m: batch._PREP_LANES.value(mode=m) for m in ("serial", "sharded")}
    verifier = _NoDevice()
    grow, raw, pending = batch._dispatch_uniq(
        sess, verifier, SigCache(), batch._UniqState()
    )
    assert len(grow) == n and len(raw) == 32 * n and verifier.launched == [n]
    other = "serial" if mode == "sharded" else "sharded"
    assert batch._PREP_LANES.value(mode=mode) - before[mode] == n
    assert batch._PREP_LANES.value(mode=other) == before[other]
    assert verifier.phases.report()["host_prep"]["calls"] == 2


# -- the coin tables' key and hash (native/block.hpp NOutPoint, PR 40) ------


def _golden_coins():
    ns = (0, 1, 2, 3, 255, 256, 0x01020304, 0x7FFFFFFE)
    spks = (b"", b"\x51", b"\x00\x14" + bytes(range(20)),
            b"\x76\xa9\x14" + bytes(20) + b"\x88\xac", b"\x51\x20" + bytes(range(32)),
            b"\x6a", b"\xa9\x14" + b"\x07" * 20 + b"\x87", bytes(range(200)))
    return [(hashlib.sha256(b"golden-coin-%d" % i).digest(), ns[i], 1001 * (i + 1) - 1,
             100 + i, i % 3 == 0, spks[i]) for i in range(8)]


def test_view_digest_of_a_fixed_view_is_pinned():
    """The key's bytes are txid || n little-endian, whatever holds them: the
    digest below was read off the `std::string`-keyed view (PR 39's tree),
    and the same bytes hashed here by hand give it too."""
    view = NB.NativeCoinsView()
    view.add_coins_batch(_golden_coins())
    want = bytearray(32)
    for txid, n, value, height, cb, spk in _golden_coins():
        d = hashlib.sha256(
            txid + n.to_bytes(4, "little") + value.to_bytes(8, "little")
            + height.to_bytes(4, "little") + bytes([cb]) + spk).digest()
        want = bytearray(a ^ b for a, b in zip(want, d))
    assert len(view) == 8
    assert view.digest() == bytes(want)
    assert view.digest().hex() == (
        "0c7a84524d75b2fd582b55105919d320fd97bb0ba9f549d046d09835e685d6d8")
    assert view.clone().digest() == view.digest()


def _insert_and_probe_s(txids: np.ndarray) -> float:
    """Seconds to insert the coins (txids[i], i & 3) in bulk, clone the view
    (an insert a coin again) and look every fourth one up."""
    import time

    from bitcoinconsensus_tpu.core.tx import OutPoint

    n = len(txids)
    view = NB.NativeCoinsView()
    t0 = time.perf_counter()
    view.add_coins_arrays(
        txids=txids.reshape(-1), ns=np.arange(n, dtype=np.int32) & 3,
        values=np.full(n, 5000, np.int64), heights=np.ones(n, np.int32),
        coinbases=np.zeros(n, np.int32), spk_blob=np.full(n, 0x51, np.uint8),
        spk_offs=np.arange(n + 1, dtype=np.int64))
    clone = view.clone()
    rows = [r.tobytes() for r in txids[::4]]
    found = sum(clone.get(OutPoint(row, (4 * j) & 3)) is not None
                for j, row in enumerate(rows))
    took = time.perf_counter() - t0
    assert len(view) == len(clone) == n and found == len(rows)
    return took


def test_chosen_outpoints_cannot_flood_the_coin_table():
    """50,000 outpoints whose txids share their first 16 bytes, and 50,000
    whose txids are a counter (all but 3 bytes equal), go in and are found in
    about the time 50,000 random ones take. A hash that sliced the txid, or
    one whoever mines the txids could run ahead of time, would put a set of
    them in one bucket: 1.25e9 key compares, hundreds of times slower. (A set
    that collides under libstdc++'s unsalted hash of the old string key is
    not cheap to make: its murmur mix yields to differentials pairwise, 8
    strings of 36 bytes, not 50,000.)"""
    n = 50_000
    rng = np.random.Generator(np.random.PCG64(40))
    random_ids = np.frombuffer(rng.bytes(32 * n), dtype=np.uint8).reshape(n, 32)
    shared_head = random_ids.copy()
    shared_head[:, :16] = shared_head[0, :16]
    counter = np.zeros((n, 32), dtype=np.uint8)
    counter[:] = random_ids[1]
    counter[:, 29:] = np.arange(n, dtype=">u4").view(np.uint8).reshape(n, 4)[:, 1:]
    _insert_and_probe_s(random_ids[:2000])  # first touch of the code paths
    base = min(_insert_and_probe_s(random_ids) for _ in range(2))
    for ids in (shared_head, counter):
        assert _insert_and_probe_s(ids) < 5 * base


def test_views_filled_in_any_order_are_equal_by_length_and_digest():
    """Nothing reads the order a table holds its coins in (it changes with
    the process's salt): views are compared by `len` and `digest`."""
    a, b = NB.NativeCoinsView(), NB.NativeCoinsView()
    coins = _golden_coins()
    a.add_coins_batch(coins)
    b.add_coins_batch(coins[::-1])
    assert (len(a), a.digest()) == (len(b), b.digest())


def _accounting_reason(vouts, cb_vouts=None):
    """`nat_block_accounting` alone (no `check_block` in front of it, as a
    caller of the C ABI or the fuzzer's target may do) on a block whose one
    transaction spends a funded coin to `vouts`."""
    from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
    from bitcoinconsensus_tpu.utils.blockgen import build_block

    funding = hashlib.sha256(b"money-range").digest()
    view = NB.NativeCoinsView()
    view.add_coins_batch([(funding, 0, 50_000, 100, False, b"\x51")])
    tx = Tx(2, [TxIn(OutPoint(funding, 0))], [TxOut(v, b"\x51") for v in vouts], 0)
    block = build_block([tx], 710_000, witness_commitment=False)
    if cb_vouts is not None:
        block.vtx[0].vout = [TxOut(v, b"\x51") for v in cb_vouts]
        block.vtx[0].invalidate_caches()
    nblk = NB.NativeBlock(block.serialize())
    before = (len(view), view.digest())
    reason = nblk.accounting(view, 710_000, 0)[0]
    assert (len(view), view.digest()) == before
    return reason


_MAX_MONEY = 21_000_000 * 100_000_000


@pytest.mark.parametrize("vouts,cb_vouts,reason", [
    ([40_000], None, None),
    # two terms in range, a sum out of it
    ([_MAX_MONEY, 1], None, "bad-txns-txouttotal-toolarge"),
    # terms whose i64 sum would wrap (signed overflow: UB the sanitizer build
    # aborts on): refused at the first term, nothing added
    ([2**62, 2**62], None, "bad-txns-vout-toolarge"),
    ([2**63 - 1, 2**63 - 1, 2], None, "bad-txns-vout-toolarge"),
    ([-1], None, "bad-txns-vout-negative"),
    # the coinbase's own sum, for the reward cap
    ([40_000], [2**62, 2**62], "bad-txns-vout-toolarge"),
    ([40_000], [_MAX_MONEY, _MAX_MONEY], "bad-txns-txouttotal-toolarge"),
    ([40_000], [_MAX_MONEY], "bad-cb-amount"),
])
def test_accounting_alone_sums_outputs_inside_money_range(vouts, cb_vouts, reason):
    assert _accounting_reason(vouts, cb_vouts) == reason
