"""Differential validation of the PRODUCTION pallas path on real TPU.

The CI suite equality-tests pallas-vs-XLA in interpret mode on CPU
(tests/pallas_equality_check.py); this script closes the remaining gap by
running a large adversarial mixed batch through the REAL compiled pallas
kernel on the TPU and comparing every verdict against the native host
oracle (C++ secp, itself differential-tested against the reference
library). Run on hardware:

    python scripts/tpu_differential.py [n_checks=8192] [seed=7]

Exits non-zero on any divergence; prints one JSON line.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def build_adversarial_checks(n: int, seed: int):
    """Mixed valid/invalid checks covering every host-parse and device
    branch: corrupted sigs/messages, wrong-parity and hybrid (0x06/0x07)
    keys, non-residue x, out-of-range scalars, r+n secondary targets
    (probabilistically), empty/short blobs."""
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
    from bitcoinconsensus_tpu.utils.hashes import tagged_hash

    rng = random.Random(seed)
    checks = []

    def flip(b: bytes, i: int) -> bytes:
        return b[:i] + bytes([b[i] ^ 1]) + b[i + 1 :]

    for i in range(n):
        sk = rng.randrange(1, H.N)
        msg = hashlib.sha256(b"diff-%d-%d" % (seed, i)).digest()
        case = i % 8
        if case in (0, 1):  # valid ECDSA (alternating key compression)
            pub = H.pubkey_create(sk, compressed=bool(case))
            sig = H.sign_ecdsa(sk, msg)
            checks.append(SigCheck("ecdsa", (pub, sig, msg)))
        elif case == 2:  # corrupted ECDSA sig
            pub = H.pubkey_create(sk)
            sig = flip(H.sign_ecdsa(sk, msg), 9)
            checks.append(SigCheck("ecdsa", (pub, sig, msg)))
        elif case == 3:  # valid Schnorr
            xpk, _ = H.xonly_pubkey_create(sk)
            checks.append(SigCheck("schnorr", (xpk, H.sign_schnorr(sk, msg), msg)))
        elif case == 4:  # Schnorr wrong message
            xpk, _ = H.xonly_pubkey_create(sk)
            checks.append(
                SigCheck("schnorr", (xpk, H.sign_schnorr(sk, msg), flip(msg, 0)))
            )
        elif case == 5:  # valid taproot tweak (BIP86 shape)
            px, parity = H.xonly_pubkey_create(sk)
            d_even = sk if parity == 0 else H.N - sk
            t = int.from_bytes(tagged_hash("TapTweak", px), "big") % H.N
            qx, qpar = H.xonly_pubkey_create((d_even + t) % H.N)
            checks.append(
                SigCheck("tweak", (qx, qpar, px, t.to_bytes(32, "big")))
            )
        elif case == 6:  # tweak with flipped output parity -> invalid
            px, parity = H.xonly_pubkey_create(sk)
            d_even = sk if parity == 0 else H.N - sk
            t = int.from_bytes(tagged_hash("TapTweak", px), "big") % H.N
            qx, qpar = H.xonly_pubkey_create((d_even + t) % H.N)
            checks.append(
                SigCheck("tweak", (qx, qpar ^ 1, px, t.to_bytes(32, "big")))
            )
        else:  # structurally broken blobs (host-parse rejects) — drawn
            # from the seeded rng so a divergence stays reproducible
            kind = rng.choice(["ecdsa", "schnorr"])
            if kind == "ecdsa":
                pub = bytes([rng.choice([0x05, 0x02])]) + rng.randbytes(32)
                checks.append(SigCheck("ecdsa", (pub, rng.randbytes(70), msg)))
            else:
                checks.append(
                    SigCheck("schnorr", (rng.randbytes(31), rng.randbytes(64), msg))
                )
    return checks


def host_oracle(chk) -> bool:
    from bitcoinconsensus_tpu import native_bridge

    S = native_bridge.NativeSecp
    if chk.kind == "ecdsa":
        pub, sig, msg = chk.data
        return S.verify_ecdsa(pub, sig, msg)
    if chk.kind == "schnorr":
        pk, sig, msg = chk.data
        return S.verify_schnorr(pk, sig, msg)
    q, parity, p, t = chk.data
    return S.tweak_add_check(q, parity, p, t)


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 7
    import chip_guard
    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    dev = chip_guard.require_tpu()
    if not native_bridge.available():
        sys.exit(f"native host oracle required: {native_bridge.why_absent()}")
    checks = build_adversarial_checks(n, seed)
    print(f"built {n} adversarial checks", file=sys.stderr)

    v = TpuSecpVerifier()
    got = np.asarray(v.verify_checks(checks))
    # The host rung IS this oracle: a run that fell back to it would agree
    # with itself. Require that it did not before comparing.
    chip_guard.assert_clean(v, "tpu_differential.py")
    want = np.fromiter((host_oracle(c) for c in checks), dtype=bool, count=n)
    diverged = np.nonzero(got != want)[0]
    out = {
        "metric": "tpu_pallas_differential",
        "n": n,
        "seed": seed,
        "device": dev,
        "dispatches": chip_guard.dispatches(),
        "valid_fraction": round(float(want.mean()), 4),
        "diverged": int(diverged.size),
    }
    print(json.dumps(out))
    if diverged.size:
        for i in diverged[:10]:
            print(f"  lane {i}: kind={checks[i].kind} device={got[i]} "
                  f"host={want[i]}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
