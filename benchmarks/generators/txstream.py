"""An open-loop stream of transactions for a served cell, from a seed.

A request is one transaction: the client sends its inputs as REQ frames
back to back at its due time. The traffic file fixes the rate, the tenants and their
Zipf shares, the distribution of inputs per transaction, the script kinds
and the share of transactions with one corrupted input. Counts never
depend on the seed: the window holds the same multiset of transaction
sizes, of Poisson gaps (`stats.stratified_gaps`), of tenants and of kinds
for every seed, in another order. A warm-up stretch of the same traffic
comes before the window and is built the same way, apart.
"""

from __future__ import annotations

import math
import random
from typing import Dict, List

from ..harness import signer
from ..harness.stats import quota, stratified_gaps

VERSION = 1
USES_SECONDS = True


def tx_sizes(n_tx: int, dist: Dict[str, float]) -> List[int]:
    """`n_tx` transaction sizes: each bucket of the distribution ("1",
    "3-4", "11-50") gets its exact quota, spread evenly over its range."""
    sizes: List[int] = []
    for bucket, count in sorted(quota(n_tx, dist).items(), key=lambda kv: int(kv[0].split("-")[0])):
        lo, _, hi = bucket.partition("-")
        lo, hi = int(lo), int(hi or lo)
        span = hi - lo + 1
        # stride coprime with the span walks the whole range evenly
        stride = next(s for s in (7, 5, 3, 1) if math.gcd(s, span) == 1)
        sizes.extend(lo + (i * stride) % span for i in range(count))
    return sizes


def tenant_names(n: int) -> List[str]:
    return [f"tenant-{k}" for k in range(n)]


def _stretch(
    config: dict, traffic: dict, seed: int, tag: str, span_s: float,
    t0: float, rid0: int,
) -> dict:
    """The requests of one stretch (warm-up or window) of `span_s` seconds."""
    rate = float(traffic["rate_tx_per_s"])
    n_tx = max(1, round(rate * span_s))
    rng = random.Random(f"{config['name']}/{traffic['name']}/{tag}/{seed}")
    sizes = tx_sizes(n_tx, traffic["inputs_per_tx"])
    rng.shuffle(sizes)
    gaps = stratified_gaps(n_tx, rate)
    rng.shuffle(gaps)
    names = tenant_names(int(traffic["tenants"]))
    zipf = {name: 1.0 / (k + 1) ** float(traffic["zipf_s"]) for k, name in enumerate(names)}
    tenants: List[str] = []
    for name, count in quota(n_tx, zipf).items():
        tenants.extend([name] * count)
    rng.shuffle(tenants)
    n_inputs = sum(sizes)
    kinds: List[str] = []
    for kind, count in quota(n_inputs, traffic["kinds"]).items():
        kinds.extend([kind] * count)
    rng.shuffle(kinds)
    lo, hi = traffic["amount_sat"]
    funded = signer.fund(
        kinds, [rng.randrange(lo, hi) for _ in range(n_inputs)],
        f"{config['name']}/{traffic['name']}/{tag}/fund/{seed}",
    )
    n_bad = round(n_tx * float(traffic["corrupt_tx_share"]))
    bad_txs = set(rng.sample(range(n_tx), n_bad))

    requests, txs, truth = [], [], {}
    due, at, rid = t0, 0, rid0
    for t in range(n_tx):
        group = funded[at : at + sizes[t]]
        at += sizes[t]
        due += gaps[t]
        # one flipped signature bit: that input fails and no other does
        victim = rng.randrange(len(group)) if t in bad_txs else None
        tx = signer.build_spend_tx(group, fee=1000, corrupt_input=victim)
        raw = tx.serialize()
        outs = [(f.amount, f.wallet.spk) for f in group]
        for i in range(len(group)):
            rid += 1
            truth[rid] = {"tx": t, "input": i, "corrupted": i == victim}
        txs.append({"raw": raw, "outs": outs})
        requests.append({
            "due": due, "tenant": tenants[t], "session": names.index(tenants[t]),
            "tx": t, "rids": list(range(rid - len(group) + 1, rid + 1)),
        })
    return {"requests": requests, "txs": txs, "truth": truth, "end": due, "rid": rid}


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    from bitcoinconsensus_tpu.core.flags import height_to_flags

    flags = height_to_flags(int(traffic["flags_height"]), extended=True)
    warm_s = float(traffic["warmup_s"])
    warm = _stretch(config, traffic, seed, "warm", warm_s, 0.0, 0)
    win = _stretch(config, traffic, seed, "window", seconds, warm_s, warm["rid"])
    return {
        "flags": flags, "warmup_s": warm_s, "seconds": seconds,
        "warm": warm, "window": win,
    }
