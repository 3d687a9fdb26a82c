"""One block of P2WSH m-of-n CHECKMULTISIG spends at the weight limit, the
coins it spends and three corrupted twins, from a seed.

Every input spends a P2WSH of a bare `m <key_1> ... <key_n> n
CHECKMULTISIG` (the configuration says 8-of-20, signed by the eight
first-pushed keys in key order: Core's top-down walk fails twelve keys
before its first success and verifies 20 pairings an input, and a
validator that pre-records every pairing the cursor could reach dispatches
m x (n - m + 1) = 104). The configuration fixes every count; the seed
picks keys, amounts, outpoints and the corrupted inputs. The twins only
exist with m > 1: one bit of the first-pushed signature flipped (the walk's
last signature finds no key), one bit of a middle signature flipped (the
walk stops half way), two adjacent signatures swapped (each valid for a
listed key, in the wrong order). Each ends its input `EVAL_FALSE`.

The block's weight, its sigop cost (by the plain reference,
`harness/sigopref.py`), the number of distinct keys and the pairings a
pre-recording validator dispatches are asserted here, against the
configuration's own figures. Returns what `generators/block.py` returns,
and `twins` as `generators/tapblock.py` does.
"""

from __future__ import annotations

import hashlib
import random
from typing import Callable, List, Optional

from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut

from ..harness import msigner, signer, sigopref

VERSION = 1
USES_SECONDS = False

MAX_BLOCK_WEIGHT = 4_000_000  # consensus/consensus.h


def _flip(sig: bytes) -> bytes:
    """One bit inside r: the signature still parses and verifies against no key."""
    return sig[:9] + bytes([sig[9] ^ 1]) + sig[10:]


def corruptions(m: int) -> dict:
    """name -> the twin's signatures from the sound ones (push order)."""
    mid = m // 2

    def first(sigs: List[bytes]) -> List[bytes]:
        return [_flip(sigs[0])] + sigs[1:]

    def middle(sigs: List[bytes]) -> List[bytes]:
        return sigs[:mid] + [_flip(sigs[mid])] + sigs[mid + 1:]

    def swapped(sigs: List[bytes]) -> List[bytes]:
        return sigs[:mid - 1] + [sigs[mid], sigs[mid - 1]] + sigs[mid + 1:]

    return {"first-signature": first, "middle-signature": middle, "swapped-signatures": swapped}


def _spend(coins, amounts, outpoints, pay_to: bytes, fee: int,
           corrupt: Optional[tuple] = None) -> Tx:
    """`corrupt` = (input of this transaction, what to make of its signatures)."""
    tx = Tx(
        version=2, vin=[TxIn(op) for op in outpoints],
        vout=[TxOut(sum(amounts) - fee, pay_to)], locktime=0,
    )
    for i, (coin, amount) in enumerate(zip(coins, amounts)):
        coin.sign_input(tx, i, amount)
    if corrupt is not None:
        i, how = corrupt
        dummy, *sigs, script = tx.vin[i].witness  # BIP 143 commits to no witness
        tx.vin[i].witness = [dummy] + how(sigs) + [script]
        tx.invalidate_caches()
    return tx


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    blk = config["block"]
    n_inputs, n_txs, per_tx = int(blk["inputs"]), int(blk["txs"]), int(blk["inputs_per_tx"])
    n_keys, n_sigs = int(blk["keys"]), int(blk["sigs"])
    first = int(blk["signing_key"]) - 1  # the configuration counts push positions from 1
    if n_txs * per_tx != n_inputs:
        raise ValueError(f"{n_txs} txs of {per_tx} inputs are not {n_inputs} inputs")
    if not 2 <= n_sigs <= n_keys - first:
        raise ValueError("the twins need two signatures or more, by listed keys")
    tag = f"{config['name']}/multisigblock/{seed}"
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

    def record(tx: Tx, t: int) -> dict:
        at = slice(starts[t], starts[t] + per_tx)
        return {"raw": tx.serialize(),
                "outs": [(a, c.spk) for a, c in zip(amounts[at], coins[at])]}

    txs = [spend(t) for t in range(n_txs)]
    block = signer.build_block(txs, height, fees=fee * n_txs)
    records = [record(tx, t) for t, tx in enumerate(txs)]

    def twin(name: str, how: Callable, victim: int) -> dict:
        t = victim // per_tx
        bad_txs = list(txs)
        bad_txs[t] = spend(t, corrupt=(victim - starts[t], how))
        return {"name": name, "victim": victim, "kind": "p2wsh_multisig", "error": "EVAL_FALSE",
                "block": signer.build_block(bad_txs, height, fees=fee * n_txs).serialize(),
                "tx": {"index": t, **record(bad_txs[t], t)}}

    victims = rng.sample(range(n_inputs), 3)
    twins = [twin(name, how, v) for (name, how), v in zip(corruptions(n_sigs).items(), victims)]

    raw = block.serialize()
    weight = 3 * len(block.serialize(include_witness=False)) + len(raw)
    cost = sigopref.block_sigop_cost(
        sigopref.parse_tx(block.vtx[0].serialize()),
        [(sigopref.parse_tx(r["raw"]), r["outs"]) for r in records],
    )
    pairings = n_inputs * n_sigs * (n_keys - n_sigs + 1)
    if cost != int(blk["sigop_cost"]):
        raise ValueError(f"the block's sigop cost is {cost}, the configuration says {blk['sigop_cost']}")
    if pairings != int(blk["pairings"]):
        raise ValueError(f"{pairings} pairings in the cursor's reach, the configuration says {blk['pairings']}")
    w_lo, w_hi = blk["weight"]
    if not w_lo <= weight <= w_hi or weight >= MAX_BLOCK_WEIGHT:
        raise ValueError(f"the block weighs {weight}, outside [{w_lo}, {w_hi}] or over the consensus limit")

    return {
        "height": height,
        "block": raw,
        "bad_block": twins[0]["block"],
        "victim": twins[0]["victim"],
        "coins": [
            (op.hash, op.n, amount, 1, False, coin.spk)
            for op, amount, coin in zip(outpoints, amounts, coins)
        ],
        "txs": records,
        "bad_tx": twins[0]["tx"],
        "tx_start": starts,
        "kinds": ["p2wsh_multisig"] * n_inputs,
        "unseen_txs": [],
        "n_inputs": n_inputs,
        "coinbase": block.vtx[0].serialize(),
        "sigop_cost": cost,
        "weight": weight,
        "sigs": n_sigs,
        "pairings": pairings,
        "walk_pairings": n_inputs * (n_keys - first),
        "twins": twins,
    }
