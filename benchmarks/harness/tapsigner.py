"""Script-path P2TR outputs and their spends, for the generators.

`harness/signer.py` knows the key path only. This adds the tree: a leaf
script under BIP 341's leaf version 0xC0, a merkle path of seeded sibling
hashes, the tweaked output key and the control block, and tapscript
signing over `harness/ec.py` (the digest is the program's
`bip341_sighash`, as `signer.py`'s key path takes it; the plain reference,
`harness/tapref.py`, makes its own). Two wallets stand on it: BIP 342's
k-of-n `<k1> CHECKSIG <k2> CHECKSIGADD ... <kn> CHECKSIGADD <k> NUMEQUAL`
and the lone `<k> CHECKSIG`. Nothing here runs inside a measured window.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence

from bitcoinconsensus_tpu.core.script import push_data
from bitcoinconsensus_tpu.core.sighash import (
    SIGHASH_DEFAULT,
    PrecomputedTxData,
    SigVersion,
    bip341_sighash,
)
from bitcoinconsensus_tpu.core.tx import Tx

from . import ec
from .signer import _flip, _sk

LEAF_VERSION = 0xC0
OP_CHECKSIG, OP_CHECKSIGADD, OP_NUMEQUAL = 0xAC, 0xBA, 0x9C
KINDS = ("p2tr_csa_2of3", "p2tr_leaf_1")
CORRUPTIONS = ("signature", "commitment", "threshold")


def csa_script(keys: Sequence[bytes], k: int) -> bytes:
    """BIP 342's k-of-n script (Rationale, "CHECKMULTISIG's replacement")."""
    if not 1 <= k <= len(keys) <= 16:
        raise ValueError("k-of-n with 1 <= k <= n <= 16")
    out = push_data(keys[0]) + bytes([OP_CHECKSIG])
    for key in keys[1:]:
        out += push_data(key) + bytes([OP_CHECKSIGADD])
    return out + bytes([0x50 + k, OP_NUMEQUAL])


def leaf_script(key: bytes) -> bytes:
    return push_data(key) + bytes([OP_CHECKSIG])


def _varbytes(b: bytes) -> bytes:
    """`b` behind its compact size (a script is under 64 KiB)."""
    n = len(b)
    return (bytes([n]) if n < 0xFD else b"\xfd" + n.to_bytes(2, "little")) + b


class TapLeaf:
    """One leaf of a tree under an internal key: the leaf's script, the
    sibling hashes on its way to the root (leaf first), and from them the
    output key, its parity and the control block."""

    def __init__(self, internal_sk: int, script: bytes, siblings: Sequence[bytes]):
        px, py_odd = ec.xonly_pubkey_create(internal_sk)
        self.script, self.siblings, self.internal = script, list(siblings), px
        self.leaf_hash = k = ec.tagged_hash("TapLeaf", bytes([LEAF_VERSION]) + _varbytes(script))
        for sib in self.siblings:
            k = ec.tagged_hash("TapBranch", k + sib if k < sib else sib + k)
        t = int.from_bytes(ec.tagged_hash("TapTweak", px + k), "big")
        if t >= ec.N:
            raise ValueError("tweak out of range")
        d_even = ec.N - internal_sk if py_odd else internal_sk
        # Q = lift_x(P) + t*G = (d_even + t)*G
        self.output_key, self.parity = ec.xonly_pubkey_create((d_even + t) % ec.N)
        self.spk = b"\x51\x20" + self.output_key

    def control(self, siblings: Optional[Sequence[bytes]] = None) -> bytes:
        path = self.siblings if siblings is None else siblings
        return bytes([LEAF_VERSION | self.parity]) + self.internal + b"".join(path)

    def sighash(self, tx: Tx, n_in: int, txdata: PrecomputedTxData,
                hash_type: int = SIGHASH_DEFAULT) -> bytes:
        """The BIP 341 digest of a tapscript signature of this leaf (no
        annex, no OP_CODESEPARATOR executed)."""
        return bip341_sighash(tx, n_in, hash_type, SigVersion.TAPSCRIPT, txdata, False, b"",
                              tapleaf_hash=self.leaf_hash, codeseparator_pos=0xFFFFFFFF)


class Wallet:
    """Key material for one script-path output of `kind`, from a seed
    string; the interface of `signer.Wallet`. `empty` is the key of a
    2-of-3 that does not sign (0, 1 or 2, in script order)."""

    def __init__(self, seed: str, kind: str, depth: int = 2, empty: int = 0):
        if kind not in KINDS:
            raise ValueError(f"unknown script-path kind {kind!r}")
        self.kind = kind
        n = 3 if kind == "p2tr_csa_2of3" else 1
        self.sks = [_sk(f"{seed}/k{i}") for i in range(n)]
        keys = [ec.xonly_pubkey_create(sk)[0] for sk in self.sks]
        script = csa_script(keys, 2) if n == 3 else leaf_script(keys[0])
        siblings = [hashlib.sha256(f"{seed}/sibling{j}".encode()).digest() for j in range(depth)]
        self.leaf = TapLeaf(_sk(f"{seed}/internal"), script, siblings)
        self.spk = self.leaf.spk
        if not 0 <= empty < 3:
            raise ValueError("the key that does not sign is 0, 1 or 2")
        self.signers = [i for i in range(n) if n == 1 or i != empty]

    def sign_input(self, tx: Tx, n_in: int, amount: int,
                   txdata: Optional[PrecomputedTxData] = None, corrupt=None) -> None:
        """Fill the witness of `tx.vin[n_in]`: the signatures (the script's
        first key's on top, an empty vector for a key that does not sign),
        the script, the control block. `corrupt` is None, or one of
        `CORRUPTIONS` (True reads "signature"): one bit of the first
        signature flipped; one bit of the control block's first sibling
        flipped; the second signature replaced by the empty vector."""
        if txdata is None:
            raise ValueError("taproot signing needs PrecomputedTxData")
        corrupt = "signature" if corrupt is True else corrupt or None
        if corrupt is not None and corrupt not in CORRUPTIONS:
            raise ValueError(f"unknown corruption {corrupt!r}")
        if corrupt == "threshold" and len(self.signers) < 2:
            raise ValueError("only a 2-of-3 has a second signature to drop")
        if corrupt == "commitment" and not self.leaf.siblings:
            raise ValueError("a leaf at depth 0 has no sibling to corrupt")
        digest = self.leaf.sighash(tx, n_in, txdata)
        sigs: List[bytes] = [b""] * len(self.sks)
        for i in self.signers:
            sigs[i] = ec.sign_schnorr(self.sks[i], digest)
        if corrupt == "signature":
            sigs[self.signers[0]] = _flip(sigs[self.signers[0]], 40)
        if corrupt == "threshold":
            sigs[self.signers[1]] = b""
        siblings = self.leaf.siblings
        if corrupt == "commitment":
            siblings = [_flip(siblings[0], 7)] + siblings[1:]
        tx.vin[n_in].witness = sigs[::-1] + [self.leaf.script, self.leaf.control(siblings)]
        tx.invalidate_caches()
