"""Device SHA-256 vs hashlib: bit-equality over batches, midstates, and
the BIP340 challenge path (spec: crypto/sha256.cpp generic transform;
tag midstates: schnorrsig/main_impl.h:16-44, hash.cpp:89-96)."""

import hashlib
import random

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.ops.sha256 import (
    bip340_challenge,
    sha256_fixed,
    sha256d_fixed,
    tag_midstate,
)
from bitcoinconsensus_tpu.utils.hashes import tagged_hash


def _batch(rng, n, length):
    return np.frombuffer(
        bytes(rng.randrange(256) for _ in range(n * length)), dtype=np.uint8
    ).reshape(n, length)


def test_sha256_fixed_lengths():
    rng = random.Random(1)
    # Lengths straddling every padding/block boundary case.
    for length in (0, 1, 31, 32, 55, 56, 63, 64, 65, 96, 119, 120, 127, 128, 200):
        data = _batch(rng, 5, length)
        got = np.asarray(sha256_fixed(data))
        for i in range(data.shape[0]):
            want = hashlib.sha256(data[i].tobytes()).digest()
            assert got[i].tobytes() == want, f"len={length} lane={i}"


def test_sha256d():
    rng = random.Random(2)
    data = _batch(rng, 4, 80)  # block-header-sized
    got = np.asarray(sha256d_fixed(data))
    for i in range(4):
        want = hashlib.sha256(hashlib.sha256(data[i].tobytes()).digest()).digest()
        assert got[i].tobytes() == want


def test_midstate_matches_prefix_hash():
    # Hashing (tag||tag||payload) from scratch == midstate + payload.
    rng = random.Random(3)
    ms = tag_midstate("TapSighash")
    th = hashlib.sha256(b"TapSighash").digest()
    data = _batch(rng, 3, 100)
    got = np.asarray(sha256_fixed(data, midstate=ms, prefix_len=64))
    for i in range(3):
        want = hashlib.sha256(th + th + data[i].tobytes()).digest()
        assert got[i].tobytes() == want


def test_bip340_challenge_batch():
    rng = random.Random(4)
    r = _batch(rng, 6, 32)
    p = _batch(rng, 6, 32)
    m = _batch(rng, 6, 32)
    got = np.asarray(bip340_challenge(r, p, m))
    for i in range(6):
        want = tagged_hash(
            "BIP0340/challenge", r[i].tobytes() + p[i].tobytes() + m[i].tobytes()
        )
        assert got[i].tobytes() == want


def test_merkle_root_device_matches_host():
    """Device merkle == host merkle across sizes exercising every odd/even
    level shape, plus the CVE-2012-2459 mutated-flag semantics (the
    synthetic odd-duplicate pair must NOT count as mutation)."""
    from bitcoinconsensus_tpu.core.block import merkle_root, merkle_root_device

    rng = random.Random(1234)
    for n in (1, 2, 3, 4, 5, 7, 11, 16, 25, 33):
        leaves = [bytes(rng.randrange(256) for _ in range(32)) for _ in range(n)]
        assert merkle_root_device(leaves) == merkle_root(leaves)

    # duplicate siblings -> mutated on both backends
    dup = [b"\x11" * 32, b"\x11" * 32, b"\x22" * 32, b"\x33" * 32]
    host_root, host_mut = merkle_root(dup)
    dev_root, dev_mut = merkle_root_device(dup)
    assert host_mut and dev_mut and host_root == dev_root

    # odd count whose duplicated tail forms an equal pair: NOT mutated
    odd = [b"\x44" * 32, b"\x55" * 32, b"\x66" * 32]
    host_root, host_mut = merkle_root(odd)
    dev_root, dev_mut = merkle_root_device(odd)
    assert not host_mut and not dev_mut and host_root == dev_root

    assert merkle_root_device([]) == merkle_root([])


@pytest.mark.usefixtures("warm_kernel")
def test_device_challenge_prep_matches_host():
    """TpuSecpVerifier(device_challenge=True): the ops/sha256-batched
    BIP340 challenge path must produce bit-identical verdicts to the
    per-lane host hashing path across valid and corrupted lanes."""
    import __graft_entry__ as ge
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier

    checks = ge._example_checks(15)  # mixed ecdsa/schnorr/tweak, 16-lane rung
    # corrupt one schnorr sig and one schnorr pubkey
    for i in (1, 4):
        pk, sig, msg = checks[i].data
        if checks[i].kind == "schnorr":
            bad = bytearray(sig)
            bad[40] ^= 1
            checks[i] = SigCheck("schnorr", (pk, bytes(bad), msg))
    host_v = TpuSecpVerifier(min_batch=8, device_challenge=False)
    dev_v = TpuSecpVerifier(min_batch=8, device_challenge=True)
    # force the Python prep path on both (the native prep bypasses it)
    host_v._native = None
    dev_v._native = None
    got_host = host_v.verify_checks(checks)
    got_dev = dev_v.verify_checks(checks)
    assert (got_host == got_dev).all()
    assert not got_dev[1] or checks[1].kind != "schnorr"
