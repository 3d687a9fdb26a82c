"""A/B harness for verify-kernel experiments on the live TPU.

Builds a real mixed check batch (one signed spend a kind from
`utils/blockgen` -> native prep_pack), then times the pallas kernel
device-side (device-resident args, so the number is compute+readback
without the host upload) and checks verdict equality against the XLA
reference kernel. Usage:

    python scripts/kernel_ab.py [n_lanes] [tile ...]
"""

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def build_checks(n):
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
    from bitcoinconsensus_tpu.core.script import push_data
    from bitcoinconsensus_tpu.core.tx import TxOut
    from bitcoinconsensus_tpu.utils.hashes import hash160
    from bitcoinconsensus_tpu.utils.blockgen import (
        build_spend_tx, make_funded_view,
    )
    from bitcoinconsensus_tpu.core.sighash import (
        PrecomputedTxData, SIGHASH_ALL, bip143_sighash, SigVersion,
        bip341_sighash, SIGHASH_DEFAULT,
    )

    # Mixed ECDSA + Schnorr checks from one signed spend a kind; recover
    # (pubkey, sig, sighash) triples by re-deriving the sighashes.
    checks = []
    for kind in ("p2wpkh", "p2tr"):
        _, funded = make_funded_view(
            (n + 1) // 2, kinds=(kind,), seed=f"bench-{kind}")
        tx = build_spend_tx(funded, fee=1000)
        if kind == "p2wpkh":
            for i, f in enumerate(funded):
                sig, pub = tx.vin[i].witness
                code = b"\x76\xa9" + push_data(hash160(pub)) + b"\x88\xac"
                sh = bip143_sighash(code, tx, i, SIGHASH_ALL, f.amount)
                checks.append(SigCheck("ecdsa", (pub, sig[:-1], sh)))
        else:
            outs = [TxOut(f.amount, f.wallet.spk) for f in funded]
            txd = PrecomputedTxData(tx, outs)
            for i in range(len(funded)):
                sig = tx.vin[i].witness[0]
                sh = bip341_sighash(
                    tx, i, SIGHASH_DEFAULT, SigVersion.TAPROOT, txd, False, b""
                )
                pk = outs[i].script_pubkey[2:]
                checks.append(SigCheck("schnorr", (pk, sig, sh)))
    # interleave + corrupt a few so both verdicts appear
    mixed = []
    for a, b in zip(checks[: n // 2], checks[n // 2 :], strict=False):
        mixed.extend((a, b))
    mixed = mixed[:n]
    for j in range(0, n, 97):
        k, d = mixed[j].kind, mixed[j].data
        bad = d[2][:5] + bytes([d[2][5] ^ 1]) + d[2][6:]
        mixed[j] = SigCheck(k, (d[0], d[1], bad))
    return mixed


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("n_lanes", nargs="?", type=int, default=10240)
    ap.add_argument("tile", nargs="*", type=int, default=[512])
    a = ap.parse_args()
    N, TILES = a.n_lanes, a.tile

    import jax
    import numpy as np

    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    checks = build_checks(N)
    args = native_bridge.prep_pack(checks, N)
    dargs = [jax.device_put(np.asarray(a)) for a in args]
    for x in dargs:
        x.block_until_ready()

    # XLA reference verdicts (once)
    v = TpuSecpVerifier()
    ref = np.asarray(v._kernel(*dargs))
    print(f"lanes={N} valid={int(np.asarray(args[6]).sum())} "
          f"ref_ok={int(ref.sum())}")

    from bitcoinconsensus_tpu.ops.pallas_kernel import verify_tiles

    for tile in TILES:
        t0 = time.perf_counter()
        ok, needs = verify_tiles(*dargs, tile=tile)
        np.asarray(ok)
        compile_s = time.perf_counter() - t0
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            ok, needs = verify_tiles(*dargs, tile=tile)
            ok.block_until_ready(); needs.block_until_ready()
            times.append(time.perf_counter() - t0)
        ok_np, needs_np = np.asarray(ok), np.asarray(needs)
        match = np.array_equal(ok_np | needs_np, ref | needs_np)
        best = min(times)
        print(
            f"tile={tile:5d} compile={compile_s:6.1f}s best={best*1000:8.2f}ms "
            f"median={sorted(times)[2]*1000:8.2f}ms "
            f"{N/best:9.0f} lanes/s needs_host={int(needs_np.sum())} "
            f"match={match}"
        )
        assert match, "verdict mismatch vs XLA kernel"


if __name__ == "__main__":
    main()
