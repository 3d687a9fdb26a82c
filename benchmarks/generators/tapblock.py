"""One full block of the taproot era and the coins it spends, from a seed.

The configuration fixes every count: inputs, the four kinds of spend by
exact quota (key-path P2TR, script-path 2-of-3 `OP_CHECKSIGADD`, script-path
lone `OP_CHECKSIG`, P2WPKH), the multiset of inputs per transaction, the
depth of a leaf. The seed picks keys, amounts, the order of kinds and of
transaction sizes, which key of a 2-of-3 does not sign, and the three
corrupted inputs; never a count. The block's lanes by kind, its weight and
its sigop cost (by the plain reference, `harness/sigopref.py`) are asserted
here against the configuration's own figures. Returns what
`generators/block.py` returns, for the same driver, and beside it the three
corrupted twins: one bit of a signature flipped (any kind), one bit of a
control block's first merkle sibling flipped (a script-path input), the
second signature of a 2-of-3 replaced by the empty vector.
"""

from __future__ import annotations

import bisect
import hashlib
import random
from typing import Dict, List

from bitcoinconsensus_tpu.core.sighash import PrecomputedTxData
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut

from ..harness import signer, sigopref, tapsigner
from ..harness.stats import quota
from .block import _sizes

VERSION = 1
USES_SECONDS = False

# curve checks an input of each kind asks for: (ecdsa, schnorr, tweak)
CHECKS = {"p2tr_key": (0, 1, 0), "p2tr_csa_2of3": (0, 2, 1), "p2tr_leaf_1": (0, 1, 1),
          "p2wpkh": (1, 0, 0)}
# what each corruption ends an input of each kind with (Core's ScriptError)
SIGNATURE_ERROR = {"p2tr_key": "SCHNORR_SIG", "p2tr_csa_2of3": "SCHNORR_SIG",
                   "p2tr_leaf_1": "SCHNORR_SIG", "p2wpkh": "EVAL_FALSE"}


def _wallet(seed: str, kind: str, depth: int, rng: random.Random):
    if kind in tapsigner.KINDS:
        empty = rng.randrange(3) if kind == "p2tr_csa_2of3" else 0
        return tapsigner.Wallet(seed, kind, depth=depth, empty=empty)
    return signer.Wallet(seed, "p2tr" if kind == "p2tr_key" else kind)


def _spend(wallets, amounts, outpoints, pay_to: bytes, fee: int, corrupt=None) -> Tx:
    """One signed transaction of one P2TR output; `corrupt` is None or
    (input position, corruption)."""
    tx = Tx(version=2, vin=[TxIn(op) for op in outpoints],
            vout=[TxOut(sum(amounts) - fee, pay_to)], locktime=0)
    spent = [TxOut(a, w.spk) for a, w in zip(amounts, wallets)]
    txdata = PrecomputedTxData(tx, spent, force=True)
    for i, (w, amount) in enumerate(zip(wallets, amounts)):
        how = corrupt[1] if corrupt is not None and corrupt[0] == i else None
        if how is not None and w.kind not in tapsigner.KINDS:
            how = True  # `signer.Wallet` knows one corruption, its signature's
        w.sign_input(tx, i, amount, txdata=txdata, corrupt=how)
    return tx


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    blk = config["block"]
    n_inputs, depth = int(blk["inputs"]), int(blk["leaf_depth"])
    tag = f"{config['name']}/tapblock/{seed}"
    rng = random.Random(tag)
    counts = quota(n_inputs, blk["kinds"])
    if set(counts) != set(CHECKS):
        raise ValueError(f"the kinds are {sorted(CHECKS)}, the configuration gives {sorted(counts)}")
    kinds: List[str] = [k for k, c in counts.items() for _ in range(c)]
    rng.shuffle(kinds)
    sizes = _sizes(config)
    if sum(sizes) != n_inputs or len(sizes) != int(blk["txs"]):
        raise ValueError(f"inputs_per_tx gives {len(sizes)} txs and {sum(sizes)} inputs, "
                         f"the configuration says {blk['txs']} and {n_inputs}")
    rng.shuffle(sizes)
    lo, hi = blk["amount_sat"]
    amounts = [rng.randrange(lo, hi) for _ in range(n_inputs)]
    wallets = [_wallet(f"{tag}/{i}", kind, depth, rng) for i, kind in enumerate(kinds)]
    outpoints = [OutPoint(hashlib.sha256(f"{tag}/op/{i}".encode()).digest(), i & 0xFFFF)
                 for i in range(n_inputs)]
    starts, at = [], 0
    for s in sizes:
        starts.append(at)
        at += s
    fee, height = int(blk["fee_sat"]), int(blk["height"])

    def spend(t: int, corrupt=None) -> Tx:
        cut = slice(starts[t], starts[t] + sizes[t])
        pay_to = b"\x51\x20" + hashlib.sha256(f"{tag}/pay/{t}".encode()).digest()
        return _spend(wallets[cut], amounts[cut], outpoints[cut], pay_to, fee, corrupt)

    def record(tx: Tx, t: int) -> dict:
        cut = slice(starts[t], starts[t] + sizes[t])
        return {"raw": tx.serialize(),
                "outs": [(a, w.spk) for a, w in zip(amounts[cut], wallets[cut])]}

    txs = [spend(t) for t in range(len(sizes))]
    block = signer.build_block(txs, height, fees=fee * len(txs))
    records = [record(tx, t) for t, tx in enumerate(txs)]

    def twin(name: str, victim: int, error: str) -> dict:
        t = bisect.bisect_right(starts, victim) - 1
        bad_txs = list(txs)
        bad_txs[t] = spend(t, corrupt=(victim - starts[t], name))
        return {"name": name, "victim": victim, "kind": kinds[victim], "error": error,
                "block": signer.build_block(bad_txs, height, fees=fee * len(txs)).serialize(),
                "tx": {"index": t, **record(bad_txs[t], t)}}

    by_kind: Dict[str, List[int]] = {}
    for i, k in enumerate(kinds):
        by_kind.setdefault(k, []).append(i)
    v_sig = rng.randrange(n_inputs)
    v_commit = rng.choice(by_kind["p2tr_csa_2of3"] + by_kind["p2tr_leaf_1"])
    v_thresh = rng.choice(by_kind["p2tr_csa_2of3"])
    twins = [twin("signature", v_sig, SIGNATURE_ERROR[kinds[v_sig]]),
             twin("commitment", v_commit, "WITNESS_PROGRAM_MISMATCH"),
             twin("threshold", v_thresh, "EVAL_FALSE")]

    raw = block.serialize()
    weight = 3 * len(block.serialize(include_witness=False)) + len(raw)
    cost = sigopref.block_sigop_cost(
        sigopref.parse_tx(block.vtx[0].serialize()),
        [(sigopref.parse_tx(r["raw"]), r["outs"]) for r in records],
    )
    lanes = dict(zip(("ecdsa", "schnorr", "tweak"),
                     (sum(CHECKS[k][j] * c for k, c in counts.items()) for j in range(3))))
    if counts != {k: int(v) for k, v in blk["inputs_by_kind"].items()}:
        raise ValueError(f"inputs by kind {counts}, the configuration says {blk['inputs_by_kind']}")
    if lanes != {k: int(v) for k, v in blk["lanes_by_kind"].items()}:
        raise ValueError(f"lanes by kind {lanes}, the configuration says {blk['lanes_by_kind']}")
    if cost != int(blk["sigop_cost"]):
        raise ValueError(f"the block's sigop cost is {cost}, the configuration says {blk['sigop_cost']}")
    w_lo, w_hi = blk["weight"]
    if not w_lo <= weight <= w_hi:
        raise ValueError(f"the block weighs {weight}, outside [{w_lo}, {w_hi}]")

    return {
        "height": height,
        "block": raw,
        "bad_block": twins[0]["block"],
        "victim": v_sig,
        "coins": [(op.hash, op.n, amount, 1, False, w.spk)
                  for op, amount, w in zip(outpoints, amounts, wallets)],
        "txs": records,
        "bad_tx": twins[0]["tx"],
        "tx_start": starts,
        "kinds": kinds,
        "unseen_txs": [],
        "n_inputs": n_inputs,
        "coinbase": block.vtx[0].serialize(),
        "sigop_cost": cost,
        "weight": weight,
        "lanes_by_kind": lanes,
        "twins": twins,
    }
