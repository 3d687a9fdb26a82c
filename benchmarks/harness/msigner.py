"""Keys, scripts and signatures for a bare m-of-n CHECKMULTISIG behind P2WSH.

`harness/signer.py` signs the standard kinds (its multisig is a 2-of-3); a
block at the sigop-cost limit needs the largest key list the opcode takes,
20, which no standard wallet makes, and 80,000 distinct keys a block. A run
of keys is one fixed-base multiplication and one addition a key after it:
sk, sk+1, sk+2, ... have the public keys P, P+G, P+2G, ...
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence

from bitcoinconsensus_tpu.core.script import OP_CHECKMULTISIG, push_data
from bitcoinconsensus_tpu.core.sighash import SIGHASH_ALL, bip143_sighash
from bitcoinconsensus_tpu.core.tx import Tx

from . import ec


def run_bases(seed: str, runs: int, length: int) -> List[int]:
    """The first secret key of each of `runs` runs of `length` keys."""
    return [
        int.from_bytes(hashlib.sha256(f"{seed}/{i}".encode()).digest(), "big")
        % (ec.N - length) + 1
        for i in range(runs)
    ]


def key_runs(bases: Sequence[int], length: int) -> List[List[bytes]]:
    """For each base secret key sk, the compressed public keys of sk,
    sk+1, ..., sk+length-1: all points Jacobian, one inversion for the lot."""
    points = []
    for sk in bases:
        x, y = ec.g_mul(sk)
        acc = (x, y, 1)
        points.append(acc)
        for _ in range(length - 1):
            acc = ec._add_affine(acc, ec.GX, ec.GY)
            points.append(acc)
    flat = [bytes([2 + (y & 1)]) + x.to_bytes(32, "big") for x, y in ec._batch_affine(points)]
    return [flat[i * length : (i + 1) * length] for i in range(len(bases))]


def _push_num(n: int) -> bytes:
    """1..16 have opcodes of their own; past them a number is pushed as data."""
    return bytes([0x50 + n]) if 1 <= n <= 16 else push_data(bytes([n]))


def multisig_script(m: int, pubs: Sequence[bytes]) -> bytes:
    """`m <key_1> ... <key_n> n CHECKMULTISIG`, keys in push order."""
    return (_push_num(m) + b"".join(push_data(p) for p in pubs)
            + _push_num(len(pubs)) + bytes([OP_CHECKMULTISIG]))


def p2wsh(script: bytes) -> bytes:
    return b"\x00\x20" + hashlib.sha256(script).digest()


class MultisigCoin:
    """One P2WSH m-of-n output: a run of n keys from `base`, signed for by
    the keys at `signers` (positions in push order from 0, ascending, which
    is the order CHECKMULTISIG wants the signatures in)."""

    __slots__ = ("base", "signers", "script", "spk")

    def __init__(self, base: int, pubs: Sequence[bytes], signers: Sequence[int]):
        self.base, self.signers = base, tuple(signers)
        self.script = multisig_script(len(self.signers), pubs)
        self.spk = p2wsh(self.script)

    def sign_input(self, tx: Tx, n_in: int, amount: int, corrupt: bool = False) -> None:
        """Fill the witness of `tx.vin[n_in]`: `<> <sig>... <script>`,
        SIGHASH_ALL. `corrupt` flips one bit inside the first signature, so
        that it parses and verifies against no key."""
        sighash = bip143_sighash(self.script, tx, n_in, SIGHASH_ALL, amount)
        sigs = [ec.sign_ecdsa(self.base + k, sighash) + bytes([SIGHASH_ALL])
                for k in self.signers]
        if corrupt:
            sigs[0] = sigs[0][:9] + bytes([sigs[0][9] ^ 1]) + sigs[0][10:]
        tx.vin[n_in].witness = [b""] + sigs + [self.script]
        tx.invalidate_caches()

