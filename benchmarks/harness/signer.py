"""Funded outputs, signed spends and mined blocks for the generators.

A copy of the program's `utils/blockgen.py` (sound, verdict in `PERF.md`),
kept here so that no later PR can change what the traffic is, with the
signing moved to `harness/ec.py`. From the program it takes the
consensus primitives only: transaction and block types, the three sighash
functions, the merkle and proof-of-work helpers.
"""

from __future__ import annotations

import hashlib
import struct
from typing import List, Optional, Sequence

from bitcoinconsensus_tpu.core.block import (
    Block,
    BlockHeader,
    block_merkle_root,
    block_witness_merkle_root,
    check_proof_of_work,
    witness_commitment_index,
)
from bitcoinconsensus_tpu.core.script import OP_CHECKMULTISIG, OP_RETURN, push_data
from bitcoinconsensus_tpu.core.sighash import (
    SIGHASH_ALL,
    SIGHASH_DEFAULT,
    PrecomputedTxData,
    SigVersion,
    bip143_sighash,
    bip341_sighash,
    legacy_sighash,
)
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.models.validate import get_block_subsidy
from bitcoinconsensus_tpu.utils.hashes import hash160, sha256d

from . import ec

KINDS = ("p2pkh", "p2wpkh", "p2wsh_multisig", "p2tr")
REGTEST_POW_LIMIT = (1 << 255) - 1
REGTEST_BITS = 0x207FFFFF


def _sk(seed: str) -> int:
    return int.from_bytes(hashlib.sha256(seed.encode()).digest(), "big") % (ec.N - 1) + 1


def _flip(b: bytes, i: int) -> bytes:
    return b[:i] + bytes([b[i] ^ 1]) + b[i + 1 :]


class Wallet:
    """Key material for one output of `kind`, from a seed string."""

    def __init__(self, seed: str, kind: str):
        if kind not in KINDS:
            raise ValueError(f"unknown script kind {kind!r}")
        self.kind = kind
        if kind == "p2wsh_multisig":
            self.sks = [_sk(f"{seed}/k{i}") for i in range(3)]
            self.pubs = [ec.pubkey_create(sk) for sk in self.sks]
            self.witness_script = (
                b"\x52" + b"".join(push_data(p) for p in self.pubs) + b"\x53"
                + bytes([OP_CHECKMULTISIG])
            )
            self.spk = b"\x00\x20" + hashlib.sha256(self.witness_script).digest()
        elif kind == "p2tr":
            d = _sk(seed)
            px, parity = ec.xonly_pubkey_create(d)
            d_even = d if parity == 0 else ec.N - d
            t = int.from_bytes(ec.tagged_hash("TapTweak", px), "big") % ec.N
            self.out_sk = (d_even + t) % ec.N
            self.spk = b"\x51\x20" + ec.xonly_pubkey_create(self.out_sk)[0]
        else:
            self.sk = _sk(seed)
            self.pub = ec.pubkey_create(self.sk)
            h = hash160(self.pub)
            if kind == "p2pkh":
                self.spk = b"\x76\xa9" + push_data(h) + b"\x88\xac"
            else:
                self.spk = b"\x00\x14" + h

    def sign_input(
        self, tx: Tx, n_in: int, amount: int,
        txdata: Optional[PrecomputedTxData] = None, corrupt: bool = False,
    ) -> None:
        """Fill scriptSig or witness of `tx.vin[n_in]`; `corrupt` flips one
        bit inside the signature so that it parses and fails to verify."""
        if self.kind == "p2pkh":
            sighash = legacy_sighash(self.spk, tx, n_in, SIGHASH_ALL)
            sig = ec.sign_ecdsa(self.sk, sighash) + bytes([SIGHASH_ALL])
            if corrupt:
                sig = _flip(sig, 9)
            tx.vin[n_in].script_sig = push_data(sig) + push_data(self.pub)
        elif self.kind == "p2wpkh":
            code = b"\x76\xa9" + push_data(hash160(self.pub)) + b"\x88\xac"
            sighash = bip143_sighash(code, tx, n_in, SIGHASH_ALL, amount)
            sig = ec.sign_ecdsa(self.sk, sighash) + bytes([SIGHASH_ALL])
            if corrupt:
                sig = _flip(sig, 9)
            tx.vin[n_in].witness = [sig, self.pub]
        elif self.kind == "p2wsh_multisig":
            sighash = bip143_sighash(
                self.witness_script, tx, n_in, SIGHASH_ALL, amount
            )
            sigs = [
                ec.sign_ecdsa(sk, sighash) + bytes([SIGHASH_ALL])
                for sk in self.sks[:2]
            ]
            if corrupt:
                sigs[0] = _flip(sigs[0], 9)
            tx.vin[n_in].witness = [b""] + sigs + [self.witness_script]
        else:
            if txdata is None:
                raise ValueError("taproot signing needs PrecomputedTxData")
            sighash = bip341_sighash(
                tx, n_in, SIGHASH_DEFAULT, SigVersion.TAPROOT, txdata, False, b""
            )
            sig = ec.sign_schnorr(self.out_sk, sighash)
            if corrupt:
                sig = _flip(sig, 40)
            tx.vin[n_in].witness = [sig]
        tx.invalidate_caches()


class FundedOutput:
    __slots__ = ("outpoint", "wallet", "amount")

    def __init__(self, outpoint: OutPoint, wallet: Wallet, amount: int):
        self.outpoint = outpoint
        self.wallet = wallet
        self.amount = amount


def fund(kinds: Sequence[str], amounts: Sequence[int], seed: str) -> List[FundedOutput]:
    """One funded output per entry of `kinds`, keys and outpoints from `seed`."""
    return [
        FundedOutput(
            OutPoint(hashlib.sha256(f"{seed}/op/{i}".encode()).digest(), i & 0xFFFF),
            Wallet(f"{seed}/{i}", kind),
            amount,
        )
        for i, (kind, amount) in enumerate(zip(kinds, amounts, strict=True))
    ]


def build_spend_tx(
    inputs: Sequence[FundedOutput], fee: int = 1000,
    corrupt_input: Optional[int] = None,
) -> Tx:
    """One signed tx spending `inputs` to an anyone-can-spend output."""
    total = sum(f.amount for f in inputs)
    tx = Tx(
        version=2,
        vin=[TxIn(f.outpoint) for f in inputs],
        vout=[TxOut(total - fee, b"\x51")],
        locktime=0,
    )
    txdata = None
    if any(f.wallet.kind == "p2tr" for f in inputs):
        spent = [TxOut(f.amount, f.wallet.spk) for f in inputs]
        txdata = PrecomputedTxData(tx, spent, force=True)
    for i, f in enumerate(inputs):
        f.wallet.sign_input(
            tx, i, f.amount, txdata=txdata, corrupt=(i == corrupt_input)
        )
    return tx


def build_block(txs: List[Tx], height: int, fees: int, time: int = 1_600_000_000) -> Block:
    """A structurally valid block over `txs`: BIP34 coinbase, witness
    commitment, merkle root, nonce ground to the regtest target."""
    script_sig = push_data(struct.pack("<I", height).rstrip(b"\x00") or b"\x00") + b"\x00"
    coinbase = Tx(
        version=1,
        vin=[TxIn(OutPoint(b"\x00" * 32, 0xFFFFFFFF), script_sig, 0xFFFFFFFF)],
        vout=[
            TxOut(get_block_subsidy(height) + fees, b"\x51"),
            TxOut(0, bytes([OP_RETURN, 0x24]) + b"\xaa\x21\xa9\xed" + b"\x00" * 32),
        ],
        locktime=0,
    )
    coinbase.vin[0].witness = [b"\x00" * 32]
    header = BlockHeader(
        version=0x20000000, prev_hash=b"\x00" * 32, merkle_root=b"\x00" * 32,
        time=time, bits=REGTEST_BITS, nonce=0,
    )
    block = Block(header, [coinbase] + txs)
    root, _ = block_witness_merkle_root(block)
    commit = sha256d(root + coinbase.vin[0].witness[0])
    idx = witness_commitment_index(block)
    coinbase.vout[idx] = TxOut(0, coinbase.vout[idx].script_pubkey[:6] + commit)
    coinbase.invalidate_caches()
    header.merkle_root = block_merkle_root(block)[0]
    while not check_proof_of_work(block.hash, REGTEST_BITS, REGTEST_POW_LIMIT):
        header.nonce += 1
    return block
