"""The plain reference of what a chain of blocks does to a set of coins.

A Python dict of outpoint -> coin taken through raw pre-segwit blocks, with
a parser of its own and `hashlib`: no code of the program. It knows no
rule but the bookkeeping: a non-coinbase input removes the coin it names
(which must be there), every output adds one. The drivers compare the
program's view with it after a pass: the same number of coins, every coin
the chain created and left unspent present as created, every coin it
spent absent.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Dict, List, Set, Tuple

Outpoint = Tuple[bytes, int]
Coin = Tuple[int, bytes, int, bool]  # amount, scriptPubKey, height, coinbase

NULL_OUTPOINT = (b"\x00" * 32, 0xFFFFFFFF)


class _Reader:
    def __init__(self, raw: bytes):
        self.raw, self.at = raw, 0

    def take(self, n: int) -> bytes:
        out = self.raw[self.at : self.at + n]
        if len(out) != n:
            raise ValueError("block ends inside a field")
        self.at += n
        return out

    def u32(self) -> int:
        return struct.unpack("<I", self.take(4))[0]

    def i64(self) -> int:
        return struct.unpack("<q", self.take(8))[0]

    def compact(self) -> int:
        first = self.take(1)[0]
        if first < 0xFD:
            return first
        width = {0xFD: 2, 0xFE: 4, 0xFF: 8}[first]
        return int.from_bytes(self.take(width), "little")


def parse_block(raw: bytes) -> List[dict]:
    """The transactions of a raw pre-segwit block: `txid`, `vin` (a list of
    outpoints) and `vout` (a list of (amount, scriptPubKey))."""
    r = _Reader(raw)
    r.take(80)  # the header
    txs = []
    for _ in range(r.compact()):
        start = r.at
        r.u32()  # version
        n_in = r.compact()
        if n_in == 0:
            raise ValueError("a witness marker: this reference reads pre-segwit blocks only")
        vin = []
        for _ in range(n_in):
            txid, n = r.take(32), r.u32()
            r.take(r.compact())  # scriptSig
            r.u32()  # sequence
            vin.append((txid, n))
        vout = []
        for _ in range(r.compact()):
            amount = r.i64()
            vout.append((amount, r.take(r.compact())))
        r.u32()  # locktime
        txid = hashlib.sha256(hashlib.sha256(raw[start : r.at]).digest()).digest()
        txs.append({"txid": txid, "vin": vin, "vout": vout})
    if r.at != len(raw):
        raise ValueError("bytes after the last transaction")
    return txs


class ChainRef:
    """`coins` after the blocks applied so far, and every outpoint they
    spent (`spent`) or created (`created`, spent again or not)."""

    def __init__(self, coins):
        """`coins`: (txid, n, amount, height, coinbase, scriptPubKey), as
        the generators give them."""
        self.coins: Dict[Outpoint, Coin] = {
            (txid, n): (amount, spk, height, bool(cb))
            for txid, n, amount, height, cb, spk in coins
        }
        self.spent: Set[Outpoint] = set()
        self.created: Set[Outpoint] = set()

    def apply(self, raw_block: bytes, height: int) -> None:
        for tx in parse_block(raw_block):
            coinbase = tx["vin"] == [NULL_OUTPOINT]
            if not coinbase:
                for op in tx["vin"]:
                    del self.coins[op]  # KeyError: the chain spends what is not there
                    self.spent.add(op)
            for n, (amount, spk) in enumerate(tx["vout"]):
                self.coins[(tx["txid"], n)] = (amount, spk, height, coinbase)
                self.created.add((tx["txid"], n))

    def differences(self, view, untouched: int, limit: int = 5) -> List[str]:
        """What `view` (the program's, with `get(outpoint)` and `len`) holds
        otherwise than this reference, which knows all but `untouched` of
        its coins. Every coin here is looked up, and every spent outpoint."""
        from bitcoinconsensus_tpu.core.tx import OutPoint

        out: List[str] = []
        if len(view) != len(self.coins) + untouched:
            out.append(f"the view holds {len(view)} coins, the reference "
                       f"{len(self.coins)} + {untouched} untouched")
        for (txid, n), want in self.coins.items():
            coin = view.get(OutPoint(txid, n))
            got = coin and (coin.out.value, coin.out.script_pubkey, coin.height, coin.coinbase)
            if got != want:
                out.append(f"coin {txid.hex()}:{n} is {got}, the reference has {want}")
        for txid, n in self.spent - set(self.coins):
            if view.get(OutPoint(txid, n)) is not None:
                out.append(f"coin {txid.hex()}:{n} was spent and is in the view")
        return out[:limit]
