"""All five BASELINE.json configs, end-to-end, with honest cache handling.

Configs (BASELINE.md):
  1. single P2PKH input verify()            — host interpreter path
  2. 10k-input P2WPKH ECDSA batch           — verify_batch end-to-end
  3. P2WSH 2-of-3 multisig batch            — verify_batch (2 sigs/input)
  4. P2TR keypath Schnorr batch (10k)       — verify_batch (taproot API)
  5. synthetic ~4k-sigop block replay       — connect_block, <100 ms target

Every iteration uses FRESH sig/script caches: the numbers are the
cold-path cost (the cross-batch caches are benched separately as the
`cached_replay` line — the mempool→block skip the reference tree
implements with `script/sigcache.cpp`). CPU baseline numbers are read
from BASELINE_MEASURED.json (scripts/measure_cpu_baseline.py) when
present. Writes BENCH_CONFIGS.json and prints it.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.join(os.path.dirname(__file__), "..")
N_BATCH = int(os.environ.get("BENCH_N", "10000"))
BLOCK_SIGOPS = int(os.environ.get("BENCH_BLOCK_SIGOPS", "4000"))


def _fresh_caches():
    from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache

    return SigCache(1 << 20), ScriptExecutionCache(1 << 20)


def bench_single_p2pkh():
    from bitcoinconsensus_tpu import api

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_api_verify import P2PKH_SPENDING, P2PKH_SPENT

    spent = bytes.fromhex(P2PKH_SPENT)
    spending = bytes.fromhex(P2PKH_SPENDING)
    api.verify(spent, 0, spending, 0)
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < 1.0:
        for _ in range(50):
            api.verify(spent, 0, spending, 0)
        n += 50
    return n / (time.perf_counter() - t0)


def _signed_fixture(kind: str, n: int, seed: str):
    """Signed n-input tx bytes + prevout list, disk-cached (signing 10k
    inputs in host Python costs minutes; the fixture is deterministic)."""
    import pickle

    cache_dir = os.path.join(REPO, ".baseline")
    os.makedirs(cache_dir, exist_ok=True)
    # v-token invalidates cached fixtures when blockgen's signing changes.
    path = os.path.join(cache_dir, f"bench_fixture_v2_{kind}_{n}_{seed}.pkl")
    if os.path.exists(path):
        with open(path, "rb") as fh:
            return pickle.load(fh)
    from bitcoinconsensus_tpu.utils.blockgen import build_spend_tx, make_funded_view

    _, funded = make_funded_view(n, kinds=(kind,), seed=seed)
    tx = build_spend_tx(funded, fee=1000)
    fixture = (
        tx.serialize(),
        [(f.amount, f.wallet.spk) for f in funded],
    )
    with open(path, "wb") as fh:
        pickle.dump(fixture, fh)
    return fixture


def _make_batch_tx(kind: str, n: int, seed: str):
    """One n-input tx of `kind` + its BatchItems (shared PrecomputedTxData
    per tx — the validation.cpp:1538-1549 shape)."""
    from bitcoinconsensus_tpu.core.flags import (
        VERIFY_ALL_EXTENDED,
        VERIFY_ALL_LIBCONSENSUS,
    )
    from bitcoinconsensus_tpu.models.batch import BatchItem

    raw, outs_full = _signed_fixture(kind, n, seed)
    if kind == "p2tr":
        items = [
            BatchItem(raw, i, VERIFY_ALL_EXTENDED, spent_outputs=outs_full)
            for i in range(n)
        ]
    else:
        items = [
            BatchItem(
                raw,
                i,
                VERIFY_ALL_LIBCONSENSUS,
                spent_output_script=outs_full[i][1],
                amount=outs_full[i][0],
            )
            for i in range(n)
        ]
    return items


def bench_batch(kind: str, n: int, verifier, iters: int = 3):
    from bitcoinconsensus_tpu.models.batch import verify_batch

    t0 = time.time()
    items = _make_batch_tx(kind, n, seed=f"bench-{kind}")
    print(f"  built {n} {kind} inputs in {time.time()-t0:.1f}s", file=sys.stderr)

    best = float("inf")
    for _ in range(iters):
        sig, script = _fresh_caches()
        t0 = time.perf_counter()
        res = verify_batch(items, verifier=verifier, sig_cache=sig, script_cache=script)
        dt = time.perf_counter() - t0
        assert all(r.ok for r in res), f"{kind}: unexpected failures"
        best = min(best, dt)
    # Cached replay: same items, warm caches.
    sig, script = _fresh_caches()
    verify_batch(items, verifier=verifier, sig_cache=sig, script_cache=script)
    t0 = time.perf_counter()
    verify_batch(items, verifier=verifier, sig_cache=sig, script_cache=script)
    cached_dt = time.perf_counter() - t0
    return n / best, n / cached_dt


def bench_block_replay(verifier, iters: int = 5):
    """Config 5: a ~BLOCK_SIGOPS-sigop block through connect_block — the
    production path (NativeCoinsView -> native block layer + index-mode
    script phase) when the native core is on. Returns
    (best_secs, n_inputs, n_txs, phase_breakdown): the breakdown is the
    best iteration's per-phase wall clock plus the derived link/non-link
    split (`sync`+`dispatch` is the device/link wait; the round target is
    non-link < 100 ms)."""
    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.models.validate import connect_block
    from bitcoinconsensus_tpu.utils.blockgen import (
        REGTEST_POW_LIMIT,
        build_block,
        build_spend_tx,
        make_funded_view,
    )

    height = 710_000
    kinds = ("p2wpkh", "p2tr", "p2wpkh", "p2wsh_multisig")
    # p2wpkh=1 sig, p2tr=1, p2wsh 2of3=2 sigs -> 4 inputs/cycle = 5 sigs.
    n_inputs = BLOCK_SIGOPS * 4 // 5
    t0 = time.time()
    coins, funded = make_funded_view(n_inputs, kinds=kinds, seed="bench-block")
    txs = [
        build_spend_tx(funded[i : i + 8], fee=800)
        for i in range(0, n_inputs - 7, 8)
    ]
    fees = 800 * len(txs)
    block = build_block(txs, height, fees=fees)
    native = native_bridge.available()
    if native:
        nview0 = native_bridge.NativeCoinsView()
        nview0.add_coins_batch(
            [
                (txid, n, c.out.value, c.height, c.coinbase,
                 c.out.script_pubkey)
                for (txid, n), c in coins._map.items()
            ]
        )
    print(
        f"  built block: {len(txs)} txs, {n_inputs} inputs in {time.time()-t0:.1f}s",
        file=sys.stderr,
    )

    best, best_phases = float("inf"), {}
    for _ in range(iters):
        import copy

        sig, script = _fresh_caches()
        view = nview0.clone() if native else copy.deepcopy(coins)
        verifier.phases.reset()
        t0 = time.perf_counter()
        res = connect_block(
            block,
            view,
            height,
            verifier=verifier,
            pow_limit=REGTEST_POW_LIMIT,
            sig_cache=sig,
            script_cache=script,
        )
        dt = time.perf_counter() - t0
        assert res.ok, res.reason
        if dt < best:
            best = dt
            rep = verifier.phases.report()
            link = sum(
                rep.get(k, {"secs": 0})["secs"] for k in ("sync", "dispatch")
            )
            tracked = sum(d["secs"] for d in rep.values())
            best_phases = {
                k: round(d["secs"] * 1000, 2) for k, d in rep.items()
            }
            best_phases["python_residual"] = round((dt - tracked) * 1000, 2)
            best_phases["total"] = round(dt * 1000, 2)
            best_phases["link_wait"] = round(link * 1000, 2)
            best_phases["non_link"] = round((dt - link) * 1000, 2)
    return best, n_inputs, len(txs), best_phases


def main() -> None:
    import chip_guard
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    chip_guard.require_tpu()
    # One dispatch per 10k-input batch: a 10k-check config rides a single
    # 10240-lane shape (pad ladder capped at 2048 steps) instead of
    # 8192+2048.
    verifier = TpuSecpVerifier(min_batch=2048, chunk=16384, pad_step=2048)
    out = {}

    # Config 1 FIRST: the one-call path never touches the device, and
    # once the TPU client has run a dispatch its background worker
    # threads contend with the GIL that every ~130us ctypes crossing
    # releases — measured 2.6k/s after device warmup vs ~7k/s before,
    # same code. Measuring before any device work is the uncontended
    # number (and matches how the reference baseline was measured: a
    # lean process doing only single calls).
    print("config 1: single P2PKH verify()", file=sys.stderr)
    out["p2pkh_single_verifies_per_sec"] = round(bench_single_p2pkh(), 1)

    # Warm the SHAPES the timed configs hit (10240 lanes for the 10k
    # batches; 16384+4096 for the multisig config, whose 5000 inputs
    # carry 2 judged + 2 speculative pairings each = 20k checks) so the
    # 15-60s pallas compiles land here, not inside a timed sample. The
    # block replay's ~6144 shape compiles in its own first iteration,
    # which the min-of-3 there already excludes.
    t0 = time.time()
    bench_batch("p2wpkh", N_BATCH, verifier, iters=1)
    bench_batch("p2wsh_multisig", N_BATCH // 2, verifier, iters=1)
    print(f"warmup (incl. compiles): {time.time()-t0:.1f}s", file=sys.stderr)

    for kind, label in (
        ("p2wpkh", "p2wpkh_10k"),
        ("p2wsh_multisig", "p2wsh_2of3_10k"),
        ("p2tr", "p2tr_keypath_10k"),
    ):
        n = N_BATCH if kind != "p2wsh_multisig" else N_BATCH // 2
        print(f"config: {label} ({n} inputs)", file=sys.stderr)
        cold, cached = bench_batch(kind, n, verifier)
        out[f"{label}_inputs_per_sec"] = round(cold, 1)
        out[f"{label}_cached_replay_per_sec"] = round(cached, 1)

    print("config 5: block replay", file=sys.stderr)
    # Same tuning as scripts/bench_block.py: one dispatch for the whole
    # block, pad ladder capped at 2048-steps so ~5.6k checks ride a 6144
    # shape.
    block_verifier = TpuSecpVerifier(min_batch=512, chunk=8192, pad_step=2048)
    secs, n_inputs, n_txs, phases = bench_block_replay(block_verifier)
    chip_guard.assert_clean(verifier, "bench_configs.py batches")
    chip_guard.assert_clean(block_verifier, "bench_configs.py block replay")
    out["block_replay_ms"] = round(secs * 1000, 1)
    out["block_replay_inputs"] = n_inputs
    out["block_replay_txs"] = n_txs
    out["block_target_ms"] = 100.0
    out["block_replay_phase_breakdown"] = phases
    out["block_replay_non_link_ms"] = phases.get("non_link")

    base_path = os.path.join(REPO, "BASELINE_MEASURED.json")
    if os.path.exists(base_path):
        with open(base_path) as fh:
            out["cpu_baseline"] = json.load(fh)

    with open(os.path.join(REPO, "BENCH_CONFIGS.json"), "w") as fh:
        json.dump(out, fh, indent=2)
        fh.write("\n")
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
