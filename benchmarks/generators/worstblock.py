"""One block at the sigop-cost limit and the coins it spends, from a seed.

Every input spends a P2WSH of a bare `m <key_1> ... <key_n> n
CHECKMULTISIG` (the configuration says 1-of-20, signed by the first-pushed
key, which Core's top-down walk tries last: 20 pairings an input, 19 of
them failing). The configuration fixes every count; the seed picks keys,
amounts, outpoints and the corrupted input. The block's sigop cost (by the
plain reference, `harness/sigopref.py`), its weight and the number of
distinct keys are asserted here, against the configuration's own figures.
Returns what `generators/block.py` returns, for the same driver.
"""

from __future__ import annotations

import hashlib
import random

from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut

from ..harness import msigner, signer, sigopref

VERSION = 1
USES_SECONDS = False

MAX_BLOCK_WEIGHT = 4_000_000  # consensus/consensus.h


def _spend(coins, amounts, outpoints, pay_to: bytes, fee: int, corrupt=None) -> Tx:
    tx = Tx(
        version=2, vin=[TxIn(op) for op in outpoints],
        vout=[TxOut(sum(amounts) - fee, pay_to)], locktime=0,
    )
    for i, (coin, amount) in enumerate(zip(coins, amounts)):
        coin.sign_input(tx, i, amount, corrupt=(i == corrupt))
    return tx


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    blk = config["block"]
    n_inputs, n_txs, per_tx = int(blk["inputs"]), int(blk["txs"]), int(blk["inputs_per_tx"])
    n_keys, n_sigs = int(blk["keys"]), int(blk["sigs"])
    first = int(blk["signing_key"]) - 1  # the configuration counts push positions from 1
    if n_txs * per_tx != n_inputs:
        raise ValueError(f"{n_txs} txs of {per_tx} inputs are not {n_inputs} inputs")
    tag = f"{config['name']}/worstblock/{seed}"
    rng = random.Random(tag)
    lo, hi = blk["amount_sat"]
    amounts = [rng.randrange(lo, hi) for _ in range(n_inputs)]
    bases = msigner.run_bases(tag, n_inputs, n_keys)
    pubs = msigner.key_runs(bases, n_keys)
    if len({p for run in pubs for p in run}) != n_inputs * n_keys:
        raise ValueError("the block's keys are not all distinct")
    signers = range(first, first + n_sigs)
    coins = [msigner.MultisigCoin(b, p, signers) for b, p in zip(bases, pubs)]
    outpoints = [
        OutPoint(hashlib.sha256(f"{tag}/op/{i}".encode()).digest(), i & 0xFFFF)
        for i in range(n_inputs)
    ]
    fee, height = int(blk["fee_sat"]), int(blk["height"])
    pay_to = b"\x00\x14" + hashlib.sha256(f"{tag}/pay".encode()).digest()[:20]  # P2WPKH
    starts = list(range(0, n_inputs, per_tx))

    def spend(t: int, corrupt=None) -> Tx:
        at = slice(starts[t], starts[t] + per_tx)
        return _spend(coins[at], amounts[at], outpoints[at], pay_to, fee, corrupt)

    txs = [spend(t) for t in range(n_txs)]
    block = signer.build_block(txs, height, fees=fee * n_txs)

    victim = rng.randrange(n_inputs)
    victim_tx = victim // per_tx
    bad_txs = list(txs)
    bad_txs[victim_tx] = spend(victim_tx, corrupt=victim - starts[victim_tx])
    bad_block = signer.build_block(bad_txs, height, fees=fee * n_txs)

    def record(tx: Tx, t: int) -> dict:
        at = slice(starts[t], starts[t] + per_tx)
        return {"raw": tx.serialize(),
                "outs": [(a, c.spk) for a, c in zip(amounts[at], coins[at])]}

    records = [record(tx, t) for t, tx in enumerate(txs)]
    raw = block.serialize()
    weight = 3 * len(block.serialize(include_witness=False)) + len(raw)
    cost = sigopref.block_sigop_cost(
        sigopref.parse_tx(block.vtx[0].serialize()),
        [(sigopref.parse_tx(r["raw"]), r["outs"]) for r in records],
    )
    if cost != int(blk["sigop_cost"]):
        raise ValueError(f"the block's sigop cost is {cost}, the configuration says {blk['sigop_cost']}")
    if weight >= MAX_BLOCK_WEIGHT:
        raise ValueError(f"the block weighs {weight}, over the consensus limit")

    return {
        "height": height,
        "block": raw,
        "bad_block": bad_block.serialize(),
        "victim": victim,
        "coins": [
            (op.hash, op.n, amount, 1, False, coin.spk)
            for op, amount, coin in zip(outpoints, amounts, coins)
        ],
        "txs": records,
        "bad_tx": {"index": victim_tx, **record(bad_txs[victim_tx], victim_tx)},
        "tx_start": starts,
        "kinds": ["p2wsh_multisig"] * n_inputs,
        "unseen_txs": [],
        "n_inputs": n_inputs,
        "coinbase": block.vtx[0].serialize(),
        "sigop_cost": cost,
        "weight": weight,
        "pairings": n_inputs * n_sigs * (n_keys - n_sigs + 1),
    }
