"""One full block and the funded coins it spends, from a seed.

The configuration fixes every count: inputs, script kinds by exact quota,
the multiset of inputs per transaction. The seed picks keys, amounts, the
order of kinds and of transaction sizes, the corrupted input and the
transactions the mempool has not seen; so every seed gives the same shapes.
Returns plain bytes, ints and lists, which `harness/trafficcache.py` keeps.
"""

from __future__ import annotations

import bisect
import random
from typing import Dict, List

from ..harness import signer
from ..harness.stats import quota

VERSION = 1
USES_SECONDS = False


def _sizes(config: dict) -> List[int]:
    sizes: List[int] = []
    for size, count in sorted(config["block"]["inputs_per_tx"].items(), key=lambda kv: int(kv[0])):
        sizes.extend([int(size)] * int(count))
    return sizes


def unseen_txs(sizes: List[int], share_seen: float, rng: random.Random) -> List[int]:
    """Indices of the transactions the mempool never saw: the same share of
    every size class, so that their inputs number the same for every seed."""
    by_size: Dict[int, List[int]] = {}
    for i, s in enumerate(sizes):
        by_size.setdefault(s, []).append(i)
    out: List[int] = []
    for s in sorted(by_size):
        members = by_size[s]
        keep = round(len(members) * (1.0 - share_seen))
        out.extend(rng.sample(members, keep))
    return sorted(out)


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    blk = config["block"]
    n_inputs = int(blk["inputs"])
    rng = random.Random(f"{config['name']}/block/{seed}")
    kinds: List[str] = []
    for kind, count in quota(n_inputs, blk["kinds"]).items():
        kinds.extend([kind] * count)
    rng.shuffle(kinds)
    sizes = _sizes(config)
    if sum(sizes) != n_inputs or len(sizes) != int(blk["txs"]):
        raise ValueError(
            f"inputs_per_tx gives {len(sizes)} txs and {sum(sizes)} inputs, "
            f"the configuration says {blk['txs']} and {n_inputs}"
        )
    rng.shuffle(sizes)
    lo, hi = blk["amount_sat"]
    amounts = [rng.randrange(lo, hi) for _ in range(n_inputs)]
    funded = signer.fund(kinds, amounts, f"{config['name']}/fund/{seed}")

    groups, at = [], 0
    for s in sizes:
        groups.append(funded[at : at + s])
        at += s
    fee = int(blk["fee_sat"])
    txs = [signer.build_spend_tx(g, fee=fee) for g in groups]
    height = int(blk["height"])
    block = signer.build_block(txs, height, fees=fee * len(txs))

    victim = rng.randrange(n_inputs)
    starts, at = [], 0
    for s in sizes:
        starts.append(at)
        at += s
    victim_tx = bisect.bisect_right(starts, victim) - 1
    bad_txs = list(txs)
    bad_txs[victim_tx] = signer.build_spend_tx(
        groups[victim_tx], fee=fee, corrupt_input=victim - starts[victim_tx]
    )
    bad_block = signer.build_block(bad_txs, height, fees=fee * len(txs))

    share_seen = float(traffic.get("precharge_share", 0.0))
    unseen = unseen_txs(sizes, share_seen, rng) if share_seen else []

    def tx_records(block_txs):
        return [
            {"raw": tx.serialize(), "outs": [(f.amount, f.wallet.spk) for f in g]}
            for tx, g in zip(block_txs, groups)
        ]

    return {
        "height": height,
        "block": block.serialize(),
        "bad_block": bad_block.serialize(),
        "victim": victim,
        "coins": [
            (f.outpoint.hash, f.outpoint.n, f.amount, 1, False, f.wallet.spk)
            for f in funded
        ],
        "txs": tx_records(txs),
        "bad_tx": {"index": victim_tx, **tx_records(bad_txs)[victim_tx]},
        "tx_start": starts,
        "kinds": kinds,
        "unseen_txs": unseen,
        "n_inputs": n_inputs,
    }
