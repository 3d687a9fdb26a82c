"""Prove the verify path on one TPU chip: `python chip_smoke.py`.

One process drives the three entry points users call, at the sizes
`BASELINE.json` names, on data built from a seed, and compares every
verdict with the host oracle (the per-input API, looped):

1. `models.batch.verify_batch` on 10,000 mixed P2WPKH / P2WSH 2-of-3 /
   P2TR-keypath inputs in one call, ~1 % of them corrupted, fresh caches;
   then the valid ones again, which must be answered from the caches with
   no dispatch.
2. `models.validate.connect_block` on the config-5 block (height 710,000,
   400 txs, 3,200 inputs) against a funded `NativeCoinsView`; then the same
   block with one signature corrupted, which must be rejected with the
   view untouched.
3. `VerifyServer` behind `IngressServer`, driven by `IngressClient`s from a
   few threads and tenants: one burst against a cold server (its sheds are
   reported, not judged), then a few hundred single-input requests against
   a fresh server, none of which may be shed.

It cannot pass below the chip: it refuses to start unless JAX reports a
TPU and the native core loaded, and after every leg `chip_guard` requires
the expected backend to have dispatched, the ladder to be on its top rung
and every retry/demotion/containment/host-fallback counter to be zero. Any
failed check ends the process non-zero with the recorded reason; no leg is
wrapped in a handler that reports and carries on.

Output: one JSON object per line, each naming the device. Set-up facts
only (counts, shapes, seconds a first and a warm launch took) — no rate,
no utilization. The last line is `{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import chip_guard

# BASELINE.json configs 2-5.
BATCH_INPUTS = 10_000
BLOCK_INPUTS = 3_200
BLOCK_HEIGHT = 710_000
SERVE_REQUESTS = 300
SERVE_COLD_REQUESTS = 24
SERVE_THREADS = 4
# One signature per serving input and at most SERVE_THREADS requests in
# flight keep every coalesced batch on the smallest pad rung: one XLA shape.
SERVE_KINDS = ("p2wpkh", "p2tr")
# The block's mix is config 5's: 3,200 inputs carry ~5.6k curve checks (a
# 2-of-3 input records four pairings), one 8,192-lane dispatch. The batch's
# mix keeps 10,000 inputs at ~13.7k checks, two dispatches of that same
# shape, so a cold run compiles one Pallas program and one XLA program.
BLOCK_KINDS = ("p2wpkh", "p2tr", "p2wpkh", "p2wsh_multisig")
BATCH_KINDS = ("p2wpkh", "p2tr") * 3 + ("p2wpkh", "p2wsh_multisig")
INPUTS_PER_TX = 8
CORRUPT_TX_SHARE = 0.08  # one bad input in 8 % of 8-input txs: ~1 % of inputs
# A cold first dispatch carries a compile: clients and sessions must wait
# for it rather than time out (their defaults are 30 s).
WAIT_S = 1200.0


class SmokeFailure(RuntimeError):
    """A leg's check failed; the process exits non-zero."""


class Smoke(NamedTuple):
    """What every leg is held to."""

    dev: dict  # chip_guard.device_info(), named on every result line
    seed: int
    backend: str  # the rung the two large legs must dispatch on: the top one
    since: Dict[str, float]  # fallback counters before the verifier existed

    def say(self, **fields) -> None:
        print(json.dumps({**fields, "device": self.dev}), flush=True)

    def assert_clean(self, where: str) -> None:
        from bitcoinconsensus_tpu.crypto.jax_backend import default_verifier

        chip_guard.assert_clean(default_verifier(), where, self.since)


def _require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def _fresh_caches():
    from bitcoinconsensus_tpu.models.sigcache import (
        ScriptExecutionCache,
        SigCache,
    )

    return SigCache(1 << 20), ScriptExecutionCache(1 << 20)


def _oracle(item) -> Tuple[bool, object, object]:
    """One item through the per-input API: (ok, Error, ScriptError)."""
    from bitcoinconsensus_tpu import api
    from bitcoinconsensus_tpu.core.script_error import ScriptError

    try:
        if item.spent_outputs is not None:
            api.verify_with_spent_outputs(
                item.spending_tx, item.input_index, item.spent_outputs,
                item.flags,
            )
        else:
            api.verify_with_flags(
                item.spent_output_script, item.amount, item.spending_tx,
                item.input_index, item.flags,
            )
    except api.ConsensusError as e:
        return False, e.code, e.script_error
    return True, api.Error.ERR_OK, ScriptError.OK


def _compare(leg: str, items: Sequence, got: Sequence) -> Dict[str, int]:
    """Every result against the oracle — verdict, transport code and script
    error — and, where the reference library is built, its verdict too."""
    from bitcoinconsensus_tpu.utils.refbridge import load_reference_lib

    ref = load_reference_lib()
    _require(
        len(got) == len(items),
        f"{leg}: {len(got)} results for {len(items)} items",
    )
    bad = []
    rejected = 0
    for i, (item, res) in enumerate(zip(items, got)):
        want = _oracle(item)
        rejected += not want[0]
        if (res.ok, res.error, res.script_error) != want:
            bad.append((i, (res.ok, res.error, res.script_error), want))
        elif ref is not None and item.spent_outputs is None:
            ref_ok, _ = ref.verify_with_flags(
                item.spent_output_script, item.amount, item.spending_tx,
                item.input_index, item.flags,
            )
            if ref_ok != res.ok:
                bad.append((i, res.ok, ("reference", ref_ok)))
    _require(
        not bad,
        f"{leg}: {len(bad)} of {len(items)} results differ from the host "
        f"oracle, first (index, got, want): {bad[:3]}",
    )
    return {
        "compared": len(items),
        "accepted": len(items) - rejected,
        "rejected": rejected,
        "mismatches": 0,
        "reference_lib": ref is not None,
    }


def _require_dispatch(leg: str, before: Dict[str, int], backend: str) -> int:
    rose = chip_guard.dispatches().get(backend, 0) - before.get(backend, 0)
    _require(
        rose > 0,
        f"{leg}: no {backend!r} dispatch happened "
        f"(before {before}, after {chip_guard.dispatches()})",
    )
    return rose


def build_items(
    n_inputs: int, kinds: Sequence[str], per_tx: int, seed: int, tag: str
) -> Tuple[List, List[int]]:
    """`n_inputs` BatchItems over kind-homogeneous `per_tx`-input txs, kinds
    interleaved, plus the indices of the corrupted ones. Three corruptions,
    seeded: a flipped signature byte, a wrong amount, a bad pubkey prefix.
    Segwit-v0 inputs ride the reference ABI's shape (script + amount),
    taproot ones the all-prevouts shape."""
    from bitcoinconsensus_tpu.core.flags import (
        VERIFY_ALL_EXTENDED,
        VERIFY_ALL_LIBCONSENSUS,
    )
    from bitcoinconsensus_tpu.models.batch import BatchItem
    from bitcoinconsensus_tpu.utils.blockgen import (
        build_spend_tx,
        make_funded_view,
    )

    rng = random.Random(f"{tag}/{seed}")
    items: List = []
    corrupted: List[int] = []
    for kind in sorted(set(kinds)):
        n_kind = n_inputs * kinds.count(kind) // len(kinds)
        _, funded = make_funded_view(
            n_kind, kinds=(kind,), seed=f"{tag}/{seed}/{kind}"
        )
        for lo in range(0, n_kind, per_tx):
            group = funded[lo : lo + per_tx]
            how = victim = None
            if rng.random() < CORRUPT_TX_SHARE * len(group) / INPUTS_PER_TX:
                victim = rng.randrange(len(group))
                how = rng.choice(
                    ("sig", "amount", "pubkey") if kind == "p2wpkh"
                    else ("sig", "amount")
                )
            tx = build_spend_tx(
                group, fee=1000, corrupt_input=victim if how == "sig" else None
            )
            if how == "pubkey":
                sig, pub = tx.vin[victim].witness
                tx.vin[victim].witness = [sig, b"\x05" + pub[1:]]
                tx.invalidate_caches()
            raw = tx.serialize()
            outs = [(f.amount, f.wallet.spk) for f in group]
            for i, (amount, spk) in enumerate(outs):
                wrong = how == "amount" and i == victim
                if how is not None and i == victim:
                    corrupted.append(len(items))
                if kind == "p2tr":
                    spent = list(outs)
                    if wrong:
                        spent[i] = (amount + 1, spk)
                    items.append(BatchItem(
                        raw, i, VERIFY_ALL_EXTENDED,
                        spent_outputs=spent if wrong else outs,
                    ))
                else:
                    items.append(BatchItem(
                        raw, i, VERIFY_ALL_LIBCONSENSUS,
                        spent_output_script=spk, amount=amount + wrong,
                    ))
    order = list(range(len(items)))
    rng.shuffle(order)
    where = {old: new for new, old in enumerate(order)}
    return [items[i] for i in order], sorted(where[i] for i in corrupted)


def leg_batch(smoke: Smoke, n_inputs: int) -> None:
    """Leg 1: one `verify_batch` call, then the cached replay."""
    from bitcoinconsensus_tpu.models.batch import verify_batch

    t0 = time.monotonic()
    items, corrupted = build_items(
        n_inputs, BATCH_KINDS, INPUTS_PER_TX, smoke.seed, "smoke-batch"
    )
    built_s = time.monotonic() - t0
    sig_cache, script_cache = _fresh_caches()
    before = chip_guard.dispatches()
    got = verify_batch(items, sig_cache=sig_cache, script_cache=script_cache)
    counts = _compare("verify_batch", items, got)
    _require(
        [i for i, r in enumerate(got) if not r.ok] == corrupted,
        "verify_batch: the rejected inputs are not exactly the corrupted ones",
    )
    device_dispatches = _require_dispatch("verify_batch", before, smoke.backend)

    # Success-only caches: the corrupted inputs are left out of the replay.
    bad = set(corrupted)
    valid = [it for i, it in enumerate(items) if i not in bad]
    before = chip_guard.dispatches()
    replay = verify_batch(valid, sig_cache=sig_cache, script_cache=script_cache)
    _require(all(r.ok for r in replay), "cached replay: a valid input failed")
    _require(
        chip_guard.dispatches() == before,
        f"cached replay dispatched: {before} -> {chip_guard.dispatches()}",
    )
    smoke.assert_clean("verify_batch leg")
    smoke.say(
        leg="verify_batch", inputs=len(items), **counts,
        corrupted=len(corrupted), dispatches={smoke.backend: device_dispatches},
        cached_replay={"inputs": len(valid), "dispatches": 0},
        build_seconds=round(built_s, 1),
    )


def leg_block(smoke: Smoke, n_inputs: int) -> None:
    """Leg 2: `connect_block` on a valid block, then on a corrupted one."""
    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.core.flags import height_to_flags
    from bitcoinconsensus_tpu.core.tx import OutPoint
    from bitcoinconsensus_tpu.models.batch import BatchItem
    from bitcoinconsensus_tpu.models.validate import connect_block
    from bitcoinconsensus_tpu.utils.blockgen import (
        REGTEST_POW_LIMIT,
        build_block,
        build_spend_tx,
        make_funded_view,
    )

    t0 = time.monotonic()
    coins, funded = make_funded_view(
        n_inputs, kinds=BLOCK_KINDS, seed=f"smoke-block/{smoke.seed}"
    )
    groups = [
        funded[i : i + INPUTS_PER_TX]
        for i in range(0, n_inputs - INPUTS_PER_TX + 1, INPUTS_PER_TX)
    ]
    spent = [f for g in groups for f in g]
    txs = [build_spend_tx(g, fee=800) for g in groups]
    fees = 800 * len(txs)
    block = build_block(txs, BLOCK_HEIGHT, fees=fees)
    victim = random.Random(f"smoke-block/{smoke.seed}").randrange(len(spent))
    bad_txs = list(txs)
    bad_txs[victim // INPUTS_PER_TX] = build_spend_tx(
        groups[victim // INPUTS_PER_TX], fee=800,
        corrupt_input=victim % INPUTS_PER_TX,
    )
    bad_block = build_block(bad_txs, BLOCK_HEIGHT, fees=fees)
    funded_view = native_bridge.NativeCoinsView()
    funded_view.add_coins_batch([
        (txid, n, c.out.value, c.height, c.coinbase, c.out.script_pubkey)
        for (txid, n), c in coins._map.items()
    ])
    built_s = time.monotonic() - t0
    flags = height_to_flags(BLOCK_HEIGHT, extended=True)

    def as_items(block_txs):
        out = []
        for g, tx in zip(groups, block_txs):
            raw = tx.serialize()
            outs = [(f.amount, f.wallet.spk) for f in g]
            out.extend(
                BatchItem(raw, i, flags, spent_outputs=outs)
                for i in range(len(g))
            )
        return out

    def connect(blk, view):
        sig_cache, script_cache = _fresh_caches()
        return connect_block(
            blk, view, BLOCK_HEIGHT, pow_limit=REGTEST_POW_LIMIT,
            sig_cache=sig_cache, script_cache=script_cache,
        )

    before = chip_guard.dispatches()
    view = funded_view.clone()
    res = connect(block, view)
    _require(res.ok, f"connect_block rejected the valid block: {res.reason}")
    counts = _compare("connect_block", as_items(txs), res.input_results)
    _require(
        all(view.get(f.outpoint) is None for f in spent)
        and all(view.get(OutPoint(tx.txid, 0)) is not None for tx in txs),
        "connect_block: the view does not show the block applied",
    )
    device_dispatches = _require_dispatch("connect_block", before, smoke.backend)

    view = funded_view.clone()
    res = connect(bad_block, view)
    _require(
        not res.ok and res.reason == "block-validation-failed"
        and res.script_failures == [victim],
        f"connect_block on the corrupted block: ok={res.ok} "
        f"reason={res.reason!r} failures={res.script_failures} "
        f"(corrupted input {victim})",
    )
    bad_counts = _compare(
        "connect_block(corrupted)", as_items(bad_txs), res.input_results
    )
    _require(
        len(view) == len(funded_view)
        and all(view.get(f.outpoint) is not None for f in spent),
        "connect_block changed the view while rejecting the block",
    )
    smoke.assert_clean("connect_block leg")
    smoke.say(
        leg="connect_block", height=BLOCK_HEIGHT, txs=len(txs),
        inputs=len(spent), **counts,
        dispatches={smoke.backend: device_dispatches},
        corrupted_block={
            "ok": res.ok, "reason": res.reason,
            "script_failures": res.script_failures,
            "mismatches": bad_counts["mismatches"], "view_unchanged": True,
        },
        build_seconds=round(built_s, 1),
    )


def _drive(port: int, items: Sequence, n_threads: int):
    """Send `items` through `n_threads` blocking clients, one tenant each.
    Returns ({index: BatchResult}, {index: shed reason}, seconds until the
    first answer)."""
    from bitcoinconsensus_tpu.serving import IngressClient, OverloadError

    t0 = time.monotonic()

    def worker(k: int):
        answered, shed, first = {}, {}, None
        with IngressClient(port=port, timeout_s=WAIT_S) as client:
            for i in range(k, len(items), n_threads):
                try:
                    answered[i] = client.verify(items[i], tenant=f"tenant-{k}")
                except OverloadError as e:
                    shed[i] = e.reason
                    continue
                if first is None:
                    first = time.monotonic() - t0
        return answered, shed, first

    answered: Dict[int, object] = {}
    shed: Dict[int, str] = {}
    firsts = []
    with ThreadPoolExecutor(n_threads) as pool:
        for fut in [pool.submit(worker, k) for k in range(n_threads)]:
            a, s, first = fut.result()
            answered.update(a)
            shed.update(s)
            if first is not None:
                firsts.append(first)
    return answered, shed, min(firsts, default=None)


def leg_serving(
    smoke: Smoke, n_requests: int, n_cold: int, n_threads: int
) -> None:
    """Leg 3: ingress -> VerifyServer -> device, single-input requests."""
    from bitcoinconsensus_tpu.serving import IngressServer, VerifyServer

    items, _ = build_items(
        n_cold + n_requests, SERVE_KINDS, 1, smoke.seed, "smoke-serve"
    )
    cold_items, judged_items = items[:n_cold], items[n_cold:]
    before = chip_guard.dispatches()

    def serve(batch):
        """`batch` through a new server and ingress, closed in the order
        the ingress documents; returns (answered, shed, first_s, pending)."""
        with VerifyServer() as srv:
            with IngressServer(srv, idle_s=WAIT_S) as ingress:
                out = _drive(ingress.port, batch, n_threads)
        return out + (srv.pending,)

    # A cold server: the first batch carries the small shape's compile, and
    # admission may shed what queues behind that sample. Reported as found;
    # whatever was answered is still held to the oracle.
    answered, cold_shed, first_s, _ = serve(cold_items)
    cold_counts = _compare(
        "serving(cold)",
        [cold_items[i] for i in sorted(answered)],
        [answered[i] for i in sorted(answered)],
    )

    # The judged requests go to a fresh server: admission windows are per
    # server, so the compile sample above does not ride along.
    shed_before = chip_guard.counter_total("consensus_serving_shed_total")
    answered, shed, _, pending = serve(judged_items)
    shed_total = (
        chip_guard.counter_total("consensus_serving_shed_total") - shed_before
    )
    _require(
        not shed and shed_total == 0,
        f"serving: {len(shed)} judged requests were shed "
        f"({sorted(set(shed.values()))}; shed counter +{shed_total:g})",
    )
    counts = _compare(
        "serving", judged_items, [answered[i] for i in range(len(judged_items))]
    )
    _require(pending == 0, f"serving: pending == {pending} after close(drain=True)")
    xla_dispatches = _require_dispatch("serving", before, "xla")
    smoke.assert_clean("serving leg")
    reasons: Dict[str, int] = {}
    for reason in cold_shed.values():
        reasons[reason] = reasons.get(reason, 0) + 1
    smoke.say(
        leg="serving", requests=len(judged_items), threads=n_threads,
        tenants=n_threads, **counts, shed=0, pending_after_close=pending,
        dispatches={"xla": xla_dispatches},
        cold_server={
            "requests": len(cold_items), "answered": cold_counts["compared"],
            "mismatches": cold_counts["mismatches"], "shed": reasons,
            "first_answer_seconds": None if first_s is None else round(first_s, 1),
        },
    )


def launch_seconds() -> List[dict]:
    """Per backend and padded shape: seconds the first launch call took
    (trace + compile or cache load) next to a warm one."""
    rows: Dict[Tuple[str, int], dict] = {}
    for s in chip_guard.samples("consensus_dispatch_launch_seconds"):
        lab = s["labels"]
        row = rows.setdefault(
            (lab["backend"], int(lab["padded"])),
            {"backend": lab["backend"], "padded": int(lab["padded"]),
             "first_seconds": None, "warm_seconds": None},
        )
        row[lab["which"] + "_seconds"] = round(s["value"], 3)
    return [rows[k] for k in sorted(rows)]


def run(
    dev: dict,
    seed: int,
    backend: str,
    batch_inputs: int = BATCH_INPUTS,
    block_inputs: int = BLOCK_INPUTS,
    serve_requests: int = SERVE_REQUESTS,
    serve_cold: int = SERVE_COLD_REQUESTS,
    serve_threads: int = SERVE_THREADS,
) -> int:
    """The three legs; `backend` is the rung the two large legs must
    dispatch on (the verifier's top one). Returns the exit code."""
    from bitcoinconsensus_tpu.crypto.jax_backend import default_verifier

    smoke = Smoke(dev, seed, backend, chip_guard.fallback_counters())
    verifier = default_verifier()
    try:
        top = verifier._resilience.ladder.levels[0]
        _require(
            top == backend,
            f"the verifier's top rung is {top!r}, expected {backend!r}",
        )
        leg_batch(smoke, batch_inputs)
        leg_block(smoke, block_inputs)
        leg_serving(smoke, serve_requests, serve_cold, serve_threads)
    except (SmokeFailure, chip_guard.ChipPathError) as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        smoke.say(
            ok=False, error=str(e), last_failure=verifier._inflight.last_failure
        )
        return 1
    smoke.say(launch_seconds=launch_seconds(), seed=seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=21,
                    help="seed for all generated keys, txs and corruptions")
    args = ap.parse_args(argv)

    dev = chip_guard.require_tpu()
    from bitcoinconsensus_tpu import native_bridge

    if not native_bridge.available():
        print(
            "refusing to run: the native host core did not load: "
            f"{native_bridge.why_absent()}",
            file=sys.stderr,
        )
        return 2
    return run(dev, args.seed, "pallas")


if __name__ == "__main__":
    sys.exit(main())
