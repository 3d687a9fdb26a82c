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
