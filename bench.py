"""Headline bench: mixed ECDSA+Schnorr verify throughput (BASELINE.json).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
North star (BASELINE.md): >= 50,000 mixed verifies/sec on one TPU v5e-1.
`vs_baseline` is value / 50_000.

All signatures are unique (no in-batch dedup flattery). End-to-end per
check: host byte parsing + lax-DER + batched modular inverse + byte-packed
pipelined device dispatch of the batched double-scalar-mult kernel.

`--stream` runs the sustained-stream config instead: a window of batches
kept in flight through `verify_checks_begin/finish`, so batch N+1's host
prep (parsing, lane packing, digests) overlaps batch N's device wait.
Steady-state verifies/sec is compared against the single-shot 1/latency
bound — the gap is the pipelining win (BENCH_r06.json).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time


TARGET = 50_000.0  # verifies/sec, driver-set north star
BATCH = 32768  # all unique; verified in ONE dispatch (see verifier note)


def build_checks(n=BATCH):
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck

    checks = []
    for i in range(n):
        sk = (i * 2654435761 + 98765) % (H.N - 1) + 1
        msg = hashlib.sha256(b"bench-%d" % i).digest()
        if i % 3 == 2:
            xpk, _ = H.xonly_pubkey_create(sk)
            sig = H.sign_schnorr(sk, msg)
            checks.append(SigCheck("schnorr", (xpk, sig, msg)))
        else:
            pub = H.pubkey_create(sk, compressed=bool(i % 2))
            sig = H.sign_ecdsa(sk, msg)
            checks.append(SigCheck("ecdsa", (pub, sig, msg)))
    return checks


def adversarial_check(verifier, checks) -> None:
    """Mixed-verdict batch through the PRODUCTION path (real backend, full
    chunk, 512-lane pallas tiles on TPU): corrupted sigs and a structurally
    invalid pubkey must fail their lanes and only their lanes."""
    import numpy as np

    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck

    adv = list(checks[: verifier._chunk])

    def corrupt_sig(c):
        pk, sig, msg = c.data
        b = bytearray(sig)
        b[len(b) // 2] ^= 1
        return SigCheck(c.kind, (pk, bytes(b), msg))

    adv[0] = corrupt_sig(adv[0])  # ECDSA: corrupted sig
    adv[2] = corrupt_sig(adv[2])  # Schnorr: corrupted sig
    pk, sig, msg = adv[4].data
    adv[4] = SigCheck("ecdsa", (b"\x05" + pk[1:], sig, msg))  # bad pubkey
    res = verifier.verify_checks(adv)
    bad = [0, 2, 4]
    assert not res[bad].any(), "corrupted lanes must fail"
    mask = np.ones(len(adv), dtype=bool)
    mask[bad] = False
    assert res[mask].all(), "valid lanes must be unaffected"
    print("adversarial mixed-verdict batch at production shape: OK", file=sys.stderr)


def run_stream(chunk: int, depth: int, batches: int) -> None:
    """Sustained-stream config: `batches` equal batches pushed through a
    `depth`-deep begin/finish window. Single-shot latency bounds the
    sequential rate at 1/latency; the stream exceeds it by overlapping
    the next batch's host prep with the in-flight device work."""
    import chip_guard
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    verifier = TpuSecpVerifier(min_batch=min(512, chunk), chunk=chunk)
    cap = verifier.lane_capacity
    t0 = time.time()
    batch = build_checks(cap)
    print(f"built {cap} unique checks in {time.time()-t0:.1f}s",
          file=sys.stderr)

    t0 = time.time()
    res = verifier.verify_checks(batch)  # warm the one padded shape
    print(f"warmup (incl. compile): {time.time()-t0:.1f}s", file=sys.stderr)
    assert res.all(), "bench signatures must verify"

    best_lat = min(_timed(lambda: verifier.verify_checks(batch))
                   for _ in range(3))

    def sequential():
        for _ in range(batches):
            assert verifier.verify_checks(batch).all()

    def pipelined():
        window = []
        for _ in range(batches):
            window.append(verifier.verify_checks_begin(batch))
            if len(window) >= depth:
                assert verifier.verify_checks_finish(window.pop(0)).all()
        while window:
            assert verifier.verify_checks_finish(window.pop(0)).all()

    # Interleave the two drivers (A/B/A/B...) so link/load drift hits
    # both equally; best-of wins the same way the headline bench does.
    seq_walls, pipe_walls = [], []
    for _ in range(3):
        seq_walls.append(_timed(sequential))
        pipe_walls.append(_timed(pipelined))
    seq_wall, pipe_wall = min(seq_walls), min(pipe_walls)
    chip_guard.assert_clean(verifier, "bench.py --stream")
    print(f"phases: {verifier.phases.report()}", file=sys.stderr)

    from bitcoinconsensus_tpu.obs import perf

    total = batches * cap
    print(
        json.dumps(
            {
                "metric": "sustained_stream_verify_throughput",
                "value": round(total / pipe_wall, 1),
                "unit": "verifies/sec",
                "sequential": round(total / seq_wall, 1),
                "stream_over_sequential": round(seq_wall / pipe_wall, 4),
                "single_shot_best": round(cap / best_lat, 1),
                "chunk": chunk,
                "depth": depth,
                "batches": batches,
                "single_shot_latency_s": round(best_lat, 6),
                "sequential_wall_s": round(seq_wall, 6),
                "stream_wall_s": round(pipe_wall, 6),
                "provenance": perf.provenance(),
            }
        )
    )


def _timed(fn):
    t0 = time.time()
    fn()
    return time.time() - t0


def main() -> None:
    import chip_guard
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--stream", action="store_true",
                    help="sustained-stream config (begin/finish window)")
    ap.add_argument("--chunk", type=int, default=8192,
                    help="stream dispatch chunk (lanes per batch + 1)")
    ap.add_argument("--depth", type=int, default=4,
                    help="stream window depth (batches in flight)")
    ap.add_argument("--batches", type=int, default=16,
                    help="stream length in batches")
    args = ap.parse_args()
    chip_guard.require_tpu()
    if args.stream:
        run_stream(args.chunk, args.depth, args.batches)
        return

    t0 = time.time()
    checks = build_checks()
    print(f"built {BATCH} unique checks in {time.time()-t0:.1f}s", file=sys.stderr)
    # The chunk is sized to the whole batch; the pallas grid still
    # iterates 512-lane tiles, so VMEM use is unchanged.
    verifier = TpuSecpVerifier(min_batch=512, chunk=BATCH)

    t0 = time.time()
    # Warm the one padded shape the timed runs hit (BATCH is an exact
    # multiple of the chunk): this is the pallas kernel compile.
    res = verifier.verify_checks(checks[: verifier._chunk])
    warm = time.time() - t0
    assert res.all(), "bench signatures must verify"
    print(f"warmup (incl. compile): {warm:.1f}s", file=sys.stderr)

    adversarial_check(verifier, checks)

    # Nine samples, best and median both recorded.
    times = []
    for _ in range(9):
        t0 = time.time()
        res = verifier.verify_checks(checks)
        times.append(time.time() - t0)
    assert res.all()
    chip_guard.assert_clean(verifier, "bench.py")
    print(f"phases: {verifier.phases.report()}", file=sys.stderr)

    from bitcoinconsensus_tpu.obs import perf

    best = min(times)
    median = sorted(times)[len(times) // 2]
    value = BATCH / best
    med_value = BATCH / median
    print(
        json.dumps(
            {
                "metric": "mixed_ecdsa_schnorr_verify_throughput",
                "value": round(value, 1),
                "unit": "verifies/sec",
                "vs_baseline": round(value / TARGET, 4),
                "median": round(med_value, 1),
                "median_vs_baseline": round(med_value / TARGET, 4),
                # Which hardware/software produced this number — a CPU
                # container figure can no longer masquerade as a v5e one.
                "provenance": perf.provenance(),
            }
        )
    )


if __name__ == "__main__":
    main()
