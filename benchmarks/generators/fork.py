"""Two branches over one fork point and the funded coins they spend from
outside themselves, from a seed: the tip a node is on (`A1`, `A2`) and the
branch that wins (`B1`, `B2`, `B3`).

The configuration fixes every count of a block (`block`: `tip-block`'s
inputs, transactions, multiset of inputs per transaction, script kinds by
exact quota) and of the fork (`fork`): how many inputs of a branch's second
block spend an output its first block created, and the share of a block's
transactions, the same of every size class, that the other branch leaves
out. `B1` is `A1` with those transactions replaced by new ones of the same
sizes and kinds, `B2` is `A2` likewise, and `B3` holds the transactions
left out of both beside new ones of the block's multiset scaled to the
inputs that remain. The seed picks keys, amounts, the order of kinds and
sizes, which transactions are left out, which pay an output forward and
which inputs take them, and the corrupted input; so every seed gives the
same shapes and the same counts, which `build` asserts. Returns plain
bytes, ints and lists, which `harness/trafficcache.py` keeps. The
background coins of the view are not here: the driver makes them in bulk.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence

from bitcoinconsensus_tpu.core.sighash import PrecomputedTxData
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut

# A program that cannot take a block off its tip fails here, as the
# generator is imported: before any traffic is built, a verifier made or a
# shape compiled.
from bitcoinconsensus_tpu.models.validate import disconnect_block  # noqa: F401

from ..harness import signer
from ..harness.stats import quota

VERSION = 1
USES_SECONDS = False

ANYONE = b"\x51"  # the output every transaction pays
# Curve checks an input of a kind sends to the device: a 2-of-3 CHECKMULTISIG
# pre-records, for each signature, the two keys its cursor can reach.
LANES = {"p2wpkh": 1, "p2tr": 1, "p2pkh": 1, "p2wsh_multisig": 4}


def spend_tx(inputs: Sequence[signer.FundedOutput], fee: int, forward=None,
             corrupt_input: Optional[int] = None) -> Tx:
    """`signer.build_spend_tx` with, where `forward` is a wallet, a second
    output that pays half of the value to it."""
    total = sum(f.amount for f in inputs) - fee
    vout = [TxOut(total, ANYONE)]
    if forward is not None:
        vout = [TxOut(total - total // 2, ANYONE), TxOut(total // 2, forward.spk)]
    tx = Tx(version=2, vin=[TxIn(f.outpoint) for f in inputs], vout=vout, locktime=0)
    txdata = None
    if any(f.wallet.kind == "p2tr" for f in inputs):
        txdata = PrecomputedTxData(
            tx, [TxOut(f.amount, f.wallet.spk) for f in inputs], force=True)
    for i, f in enumerate(inputs):
        f.wallet.sign_input(tx, i, f.amount, txdata=txdata, corrupt=(i == corrupt_input))
    return tx


def _sizes(inputs_per_tx: dict, scale: float = 1.0) -> List[int]:
    sizes: List[int] = []
    for size, count in sorted(inputs_per_tx.items(), key=lambda kv: int(kv[0])):
        n = int(count) * scale
        if n != round(n):
            raise ValueError(f"{count} transactions of {size} inputs do not scale by {scale}")
        sizes.extend([int(size)] * round(n))
    return sizes


def _starts(sizes: Sequence[int]) -> List[int]:
    out, at = [], 0
    for s in sizes:
        out.append(at)
        at += s
    return out


def left_out(sizes: Sequence[int], share: float, rng: random.Random) -> List[int]:
    """Indices of the transactions the other branch leaves out: the same
    share of every size class."""
    by_size: Dict[int, List[int]] = {}
    for t, s in enumerate(sizes):
        by_size.setdefault(s, []).append(t)
    out: List[int] = []
    for s in sorted(by_size):
        n = len(by_size[s]) * share
        if n != round(n):
            raise ValueError(f"{share} of {len(by_size[s])} transactions of {s} inputs is no count")
        out.extend(rng.sample(by_size[s], round(n)))
    return sorted(out)


class _Funder:
    """Coins from outside the branches, each under a name of its own."""

    def __init__(self, name: str, seed: int, amount_sat):
        self.tag, self.coins, self.amount_sat = f"{name}/fund/{seed}", [], amount_sat
        self.rng = random.Random(self.tag)

    def fund(self, kinds: Sequence[str]) -> List[signer.FundedOutput]:
        lo, hi = self.amount_sat
        at = len(self.coins)
        made = signer.fund(kinds, [self.rng.randrange(lo, hi) for _ in kinds],
                           f"{self.tag}/{at}")
        self.coins.extend(
            (f.outpoint.hash, f.outpoint.n, f.amount, 1, False, f.wallet.spk) for f in made)
        return made


def _records(txs: Sequence[Tx], groups) -> List[dict]:
    return [{"raw": tx.serialize(), "outs": [(f.amount, f.wallet.spk) for f in g]}
            for tx, g in zip(txs, groups, strict=True)]


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    blk, fork = config["block"], config["fork"]
    name = config["name"]
    n_inputs, n_txs, fee = int(blk["inputs"]), int(blk["txs"]), int(blk["fee_sat"])
    n_forward, share_out = int(fork["in_branch_spends"]), float(fork["left_out_share"])
    h0 = int(fork["fork_height"])
    sizes0 = _sizes(blk["inputs_per_tx"])
    if sum(sizes0) != n_inputs or len(sizes0) != n_txs:
        raise ValueError(f"inputs_per_tx gives {len(sizes0)} txs and {sum(sizes0)} inputs, "
                         f"the configuration says {n_txs} and {n_inputs}")
    rng = random.Random(f"{name}/fork/{seed}")
    funder = _Funder(name, seed, blk["amount_sat"])

    # -- branch A ---------------------------------------------------------
    def by_quota(n: int) -> List[str]:
        kinds = [k for k, c in quota(n, blk["kinds"]).items() for _ in range(c)]
        rng.shuffle(kinds)
        return kinds

    def lay_out(height: int) -> dict:
        """A block's shape: its transactions' sizes in block order, the
        transactions the other branch leaves out, and the kind of every
        input place: by exact quota over the left-out places and over the
        others, so that what each branch sends to the device has the same
        size for every seed."""
        sizes = list(sizes0)
        rng.shuffle(sizes)
        starts = _starts(sizes)
        out = left_out(sizes, share_out, rng)
        gone = set(out)
        places = {True: [], False: []}  # of transactions left out, and kept
        for t, (lo, s) in enumerate(zip(starts, sizes, strict=True)):
            places[t in gone].extend(range(lo, lo + s))
        kinds: List[Optional[str]] = [None] * n_inputs
        for group in places.values():
            for p, kind in zip(group, by_quota(len(group)), strict=True):
                kinds[p] = kind
        return {"height": height, "sizes": sizes, "starts": starts,
                "out": out, "kinds": kinds, "kept_places": places[False], "forwards": {}}

    def sign(b: dict, takes: dict) -> None:
        """Fund every place `takes` does not fill, and sign the block's
        transactions."""
        fresh = iter(funder.fund([k for p, k in enumerate(b["kinds"]) if p not in takes]))
        inputs = [takes[p] if p in takes else next(fresh) for p in range(n_inputs)]
        b["groups"] = [inputs[lo : lo + s] for lo, s in zip(b["starts"], b["sizes"], strict=True)]
        b["txs"] = [spend_tx(g, fee, b["forwards"].get(t)) for t, g in enumerate(b["groups"])]

    # A1 pays `n_forward` outputs forward, from transactions both branches
    # hold (none left out has a child in the branch), to wallets of the
    # block's kinds by quota.
    a1 = lay_out(h0 + 1)
    payers = sorted(rng.sample(sorted(set(range(n_txs)) - set(a1["out"])), n_forward))
    a1["forwards"] = {t: signer.Wallet(f"{name}/forward/{seed}/{t}", kind)
                      for t, kind in zip(payers, by_quota(n_forward), strict=True)}
    sign(a1, {})
    carried = [signer.FundedOutput(OutPoint(a1["txs"][t].txid, 1), w, a1["txs"][t].vout[1].value)
               for t, w in sorted(a1["forwards"].items())]
    # A2 takes them at places of their kinds, in transactions both branches
    # hold. A1's record must not fit A2 by its counts: another order of sizes.
    a2 = lay_out(h0 + 2)
    while a2["sizes"] == a1["sizes"]:
        a2 = lay_out(h0 + 2)
    takes: dict = {}
    for kind in sorted(LANES):
        mine = [f for f in carried if f.wallet.kind == kind]
        free = [p for p in a2["kept_places"] if a2["kinds"][p] == kind]
        takes.update(zip(rng.sample(free, len(mine)), mine, strict=True))
    sign(a2, takes)

    # -- branch B ---------------------------------------------------------
    def b_block(a):
        """`a` with each left-out transaction replaced by a new one of its
        size and kinds, spending coins of its own."""
        replace = {}
        for t in a["out"]:
            lo = a["starts"][t]
            replace[t] = funder.fund(a["kinds"][lo : lo + a["sizes"][t]])
        groups = [replace.get(t, g) for t, g in enumerate(a["groups"])]
        txs = [spend_tx(replace[t], fee) if t in replace else tx
               for t, tx in enumerate(a["txs"])]
        return {"height": a["height"], "sizes": a["sizes"], "starts": a["starts"],
                "groups": groups, "txs": txs, "new": sorted(replace)}

    b1, b2 = b_block(a1), b_block(a2)
    # B3: the transactions left out of both, then new ones of the block's
    # multiset scaled to the inputs that remain.
    late = [(a, t) for a in (a1, a2) for t in a["out"]]
    late_inputs = sum(a["sizes"][t] for a, t in late)
    new_sizes = _sizes(blk["inputs_per_tx"], (n_inputs - late_inputs) / n_inputs)
    rng.shuffle(new_sizes)
    new_inputs = funder.fund(by_quota(sum(new_sizes)))
    new_groups = [new_inputs[a : a + s] for a, s in zip(_starts(new_sizes), new_sizes, strict=True)]
    order = [("late", i) for i in range(len(late))] + [("new", i) for i in range(len(new_groups))]
    rng.shuffle(order)
    b3_groups, b3_txs, b3_new = [], [], []
    for kind, i in order:
        if kind == "late":
            a, t = late[i]
            b3_groups.append(a["groups"][t])
            b3_txs.append(a["txs"][t])
        else:
            b3_new.append(len(b3_txs))
            b3_groups.append(new_groups[i])
            b3_txs.append(spend_tx(new_groups[i], fee))
    b3_sizes = [len(g) for g in b3_groups]
    b3 = {"height": h0 + 3, "sizes": b3_sizes, "starts": _starts(b3_sizes),
          "groups": b3_groups, "txs": b3_txs, "new": b3_new}

    def mined(b, txs=None):
        txs = b["txs"] if txs is None else txs
        return signer.build_block(list(txs), b["height"], fees=fee * len(txs)).serialize()

    branch_a, branch_b = {"A1": a1, "A2": a2}, {"B1": b1, "B2": b2, "B3": b3}
    every = {**branch_a, **branch_b}
    blocks = {label: mined(b) for label, b in every.items()}

    # -- the corrupted branch ---------------------------------------------
    # One flipped signature bit in one of B2's new transactions, and a B3
    # with one more transaction, which spends that transaction's output: a
    # stream at depth 2 has begun it on B2's speculative coins when B2's
    # verdicts come in.
    victim_tx = rng.choice(b2["new"])
    at = rng.randrange(b2["sizes"][victim_tx])
    bad_txs = list(b2["txs"])
    bad_txs[victim_tx] = spend_tx(b2["groups"][victim_tx], fee, None, corrupt_input=at)
    parent = bad_txs[victim_tx]
    child = Tx(version=2, vin=[TxIn(OutPoint(parent.txid, 0))],
               vout=[TxOut(parent.vout[0].value - fee, ANYONE)], locktime=0)
    bad = {
        "B2": mined(b2, bad_txs), "B3": mined(b3, b3["txs"] + [child]),
        "victim": b2["starts"][victim_tx] + at,
        "tx": {"index": victim_tx, **_records(bad_txs, b2["groups"])[victim_tx]},
    }

    # -- the counts, every one the same for every seed ----------------------
    def lanes(b, txs):
        return sum(LANES[f.wallet.kind] for t in txs for f in b["groups"][t])

    def n_outputs(b):
        return 2 + sum(len(tx.vout) for tx in b["txs"])  # the coinbase pays one and commits

    carried_ids = {id(f) for f in carried}
    counts = {
        "inputs": {label: sum(b["sizes"]) for label, b in every.items()},
        "outputs": {label: n_outputs(b) for label, b in every.items()},
        "new_inputs": {label: sum(b["sizes"][t] for t in b["new"]) for label, b in branch_b.items()},
        "new_lanes": {label: lanes(b, b["new"]) for label, b in branch_b.items()},
        "cold_lanes": {label: lanes(a, range(n_txs)) for label, a in branch_a.items()},
        "in_branch_spends": {
            label: sum(1 for g in every[label]["groups"] for f in g if id(f) in carried_ids)
            for label in ("A2", "B2", "B3")},
        "funded": len(funder.coins),
    }
    n_out = round(n_inputs * share_out)
    want = {
        "inputs": dict.fromkeys(every, n_inputs),
        "outputs": {"A1": n_txs + n_forward + 2, "A2": n_txs + 2, "B1": n_txs + n_forward + 2,
                    "B2": n_txs + 2, "B3": n_txs + 2},
        "new_inputs": {"B1": n_out, "B2": n_out, "B3": n_inputs - 2 * n_out},
        "new_lanes": {label: sum(LANES[k] * c for k, c in quota(n, blk["kinds"]).items())
                      for label, n in (("B1", n_out), ("B2", n_out), ("B3", n_inputs - 2 * n_out))},
        "cold_lanes": dict.fromkeys(branch_a, sum(
            LANES[k] * c for n in (n_out, n_inputs - n_out)
            for k, c in quota(n, blk["kinds"]).items())),
        "in_branch_spends": {"A2": n_forward, "B2": n_forward, "B3": 0},
        "funded": 3 * n_inputs - n_forward,
    }
    if counts != want or len(b3["txs"]) != n_txs:
        raise AssertionError(f"the fork's counts are {counts}, the configuration gives {want}")

    return {
        "fork_height": h0,
        "blocks": blocks,
        "bad": bad,
        "coins": funder.coins,
        "txs": {label: _records(b["txs"], b["groups"]) for label, b in branch_b.items()},
        "tx_start": {label: b["starts"] for label, b in branch_b.items()},
        "counts": counts,
        "n_inputs": n_inputs,
    }
