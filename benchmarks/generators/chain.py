"""A chain of distinct, dependent pre-segwit blocks and the funded coins
they spend from outside themselves, from a seed.

The configuration fixes every count of a block: transactions, inputs, the
multiset of inputs per transaction, script kinds by exact quota, and how
many inputs spend an output that a transaction of the block before
created. The seed picks keys, amounts, the order of kinds and sizes, which
transactions pay an output forward and which inputs take them, and the
corrupted input; so every seed gives the same shapes. No witness anywhere:
legacy sighash for every input, no witness commitment, every block under
the pre-segwit size limit. Returns plain bytes, ints and lists, which
`harness/trafficcache.py` keeps. The background coins of the view are not
here: the driver makes them in bulk from the seed.
"""

from __future__ import annotations

import hashlib
import random
import struct
from typing import List, Optional, Sequence

from bitcoinconsensus_tpu.core.block import (
    Block,
    BlockHeader,
    block_merkle_root,
    check_proof_of_work,
)
from bitcoinconsensus_tpu.core.script import OP_CHECKMULTISIG, push_data
from bitcoinconsensus_tpu.core.sighash import SIGHASH_ALL, legacy_sighash
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut

# A program that cannot stream blocks fails here, as the generator is
# imported: before any traffic is built, a verifier made or a shape
# compiled (the drivers' set-up would say so a minute later).
from bitcoinconsensus_tpu.models.validate import (  # noqa: F401
    connect_block_stream,
    get_block_subsidy,
)
from bitcoinconsensus_tpu.utils.hashes import hash160

from ..harness import ec, signer
from ..harness.stats import quota

VERSION = 1
USES_SECONDS = False

KINDS = ("p2pkh", "p2sh_multisig")
ANYONE = b"\x51"  # the output every transaction pays: 10 bytes on the wire


class P2shMultisig:
    """Key material for one P2SH 2-of-3 bare multisig output, compressed
    keys; signs with the first two keys, in the script's order."""

    kind = "p2sh_multisig"

    def __init__(self, seed: str):
        self.sks = [signer._sk(f"{seed}/k{i}") for i in range(3)]
        self.redeem = (
            b"\x52" + b"".join(push_data(ec.pubkey_create(sk)) for sk in self.sks)
            + b"\x53" + bytes([OP_CHECKMULTISIG])
        )
        self.spk = b"\xa9\x14" + hash160(self.redeem) + b"\x87"

    def sign_input(self, tx: Tx, n_in: int, amount: int, txdata=None,
                   corrupt: bool = False) -> None:
        sighash = legacy_sighash(self.redeem, tx, n_in, SIGHASH_ALL)
        sigs = [ec.sign_ecdsa(sk, sighash) + bytes([SIGHASH_ALL]) for sk in self.sks[:2]]
        if corrupt:
            sigs[0] = signer._flip(sigs[0], 9)
        tx.vin[n_in].script_sig = (
            b"\x00" + b"".join(push_data(s) for s in sigs) + push_data(self.redeem)
        )
        tx.invalidate_caches()


def wallet(seed: str, kind: str):
    if kind not in KINDS:
        raise ValueError(f"unknown pre-segwit script kind {kind!r}")
    return P2shMultisig(seed) if kind == "p2sh_multisig" else signer.Wallet(seed, kind)


def spend_tx(inputs: Sequence[signer.FundedOutput], fee: int, forward=None,
             corrupt_input: Optional[int] = None) -> Tx:
    """One signed legacy transaction: `inputs` to the anyone-can-spend
    output and, where `forward` is a wallet, half of the value to it."""
    total = sum(f.amount for f in inputs) - fee
    vout = [TxOut(total, ANYONE)]
    if forward is not None:
        vout = [TxOut(total - total // 2, ANYONE), TxOut(total // 2, forward.spk)]
    tx = Tx(version=1, vin=[TxIn(f.outpoint) for f in inputs], vout=vout, locktime=0)
    for i, f in enumerate(inputs):
        f.wallet.sign_input(tx, i, f.amount, corrupt=(i == corrupt_input))
    return tx


def mine(txs: List[Tx], height: int, fees: int, prev_hash: bytes, time: int) -> Block:
    """A structurally valid pre-segwit block over `txs`: BIP34 coinbase, no
    witness and no commitment, merkle root, nonce ground to regtest."""
    script_sig = push_data(struct.pack("<I", height).rstrip(b"\x00") or b"\x00") + b"\x00"
    coinbase = Tx(
        version=1,
        vin=[TxIn(OutPoint(b"\x00" * 32, 0xFFFFFFFF), script_sig, 0xFFFFFFFF)],
        vout=[TxOut(get_block_subsidy(height) + fees, ANYONE)],
        locktime=0,
    )
    header = BlockHeader(
        version=0x20000000, prev_hash=prev_hash, merkle_root=b"\x00" * 32,
        time=time, bits=signer.REGTEST_BITS, nonce=0,
    )
    block = Block(header, [coinbase] + txs)
    header.merkle_root = block_merkle_root(block)[0]
    while not check_proof_of_work(block.hash, signer.REGTEST_BITS, signer.REGTEST_POW_LIMIT):
        header.nonce += 1
    return block


def _sizes(chain: dict) -> List[int]:
    sizes: List[int] = []
    for size, count in sorted(chain["inputs_per_tx"].items(), key=lambda kv: int(kv[0])):
        sizes.extend([int(size)] * int(count))
    return sizes


def _starts(sizes: Sequence[int]) -> List[int]:
    out, at = [], 0
    for s in sizes:
        out.append(at)
        at += s
    return out


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    ch = config["chain"]
    n_blocks, n_inputs = int(ch["blocks"]), int(ch["inputs"])
    n_forward, fee = int(ch["in_stream_spends"]), int(ch["fee_sat"])
    start_height = int(ch["start_height"])
    bad_index = int(ch["corrupt_block"]) - 1
    sizes0 = _sizes(ch)
    if sum(sizes0) != n_inputs or len(sizes0) + 1 != int(ch["txs"]):
        raise ValueError(
            f"inputs_per_tx gives {len(sizes0)} txs and {sum(sizes0)} inputs, "
            f"the configuration says {ch['txs']} (with the coinbase) and {n_inputs}"
        )
    if not 0 <= bad_index < n_blocks - 1:
        raise ValueError("corrupt_block needs a block behind it that spends its outputs")
    kind_counts = quota(n_inputs, ch["kinds"])
    lo, hi = ch["amount_sat"]

    coins: list = []      # funded from outside the chain, in the view before block 0
    blocks: List[bytes] = []
    records: list = []    # per block, per transaction: raw bytes and the outputs it spends
    starts: List[List[int]] = []
    kinds_by_block: List[List[str]] = []
    carried: List[signer.FundedOutput] = []  # P2PKH outputs the block before paid forward
    prev_hash, bad = b"\x00" * 32, None

    for k in range(n_blocks):
        rng = random.Random(f"{config['name']}/chain/{seed}/{k}")
        kinds: List[str] = []
        for kind in KINDS:
            kinds.extend([kind] * kind_counts.get(kind, 0))
        rng.shuffle(kinds)
        sizes = list(sizes0)
        rng.shuffle(sizes)
        tx_start = _starts(sizes)
        # The inputs that spend what the block before paid forward: P2PKH
        # places, as many as there are such outputs.
        p2pkh_places = [i for i, kind in enumerate(kinds) if kind == "p2pkh"]
        takes = dict(zip(rng.sample(p2pkh_places, len(carried)), carried, strict=True))
        inputs: List[signer.FundedOutput] = []
        for i, kind in enumerate(kinds):
            if i in takes:
                inputs.append(takes[i])
                continue
            tag = f"{config['name']}/fund/{seed}/{k}/{i}"
            f = signer.FundedOutput(
                OutPoint(hashlib.sha256(f"{tag}/op".encode()).digest(), i & 0xFFFF),
                wallet(tag, kind), rng.randrange(lo, hi),
            )
            coins.append((f.outpoint.hash, f.outpoint.n, f.amount, 1, False, f.wallet.spk))
            inputs.append(f)
        # The transactions that pay an output forward to the next block.
        forwards = {
            t: signer.Wallet(f"{config['name']}/forward/{seed}/{k}/{t}", "p2pkh")
            for t in rng.sample(range(len(sizes)), n_forward)
        }
        groups = [inputs[a : a + s] for a, s in zip(tx_start, sizes, strict=True)]
        txs = [spend_tx(g, fee, forwards.get(t)) for t, g in enumerate(groups)]
        carried = [
            signer.FundedOutput(OutPoint(txs[t].txid, 1), w, txs[t].vout[1].value)
            for t, w in sorted(forwards.items())
        ]
        height, when = start_height + k, 1_467_000_000 + 600 * k
        block = mine(txs, height, fee * len(txs), prev_hash, when)
        raw = block.serialize()
        if len(raw) >= int(ch["max_block_bytes"]):
            raise ValueError(f"block {k} is {len(raw)} bytes, over the pre-segwit limit")
        prev_hash = block.hash

        def tx_records(block_txs):
            return [
                {"raw": tx.serialize(), "outs": [(f.amount, f.wallet.spk) for f in g]}
                for tx, g in zip(block_txs, groups, strict=True)
            ]

        if k == bad_index:
            # One flipped signature bit, in a transaction that pays nothing
            # forward: its txid changes with its scriptSig, and the block
            # behind must still find the outputs it spends.
            victim_tx = rng.choice([t for t in range(len(sizes)) if t not in forwards])
            at = rng.randrange(sizes[victim_tx])
            bad_txs = list(txs)
            bad_txs[victim_tx] = spend_tx(groups[victim_tx], fee, None, corrupt_input=at)
            bad_raw = mine(bad_txs, height, fee * len(txs), block.header.prev_hash, when).serialize()
            bad = {
                "index": bad_index, "block": bad_raw, "victim": tx_start[victim_tx] + at,
                "tx": {"index": victim_tx, **tx_records(bad_txs)[victim_tx]},
            }
        blocks.append(raw)
        records.append(tx_records(txs))
        starts.append(tx_start)
        kinds_by_block.append(kinds)

    return {
        "start_height": start_height,
        "blocks": blocks,
        "bad": bad,
        "coins": coins,
        "txs": records,
        "tx_start": starts,
        "kinds": kinds_by_block,
        "n_inputs": n_inputs,
        "n_blocks": n_blocks,
        "n_forward": n_forward,
    }
