"""The plain reference of what connecting and disconnecting blocks does to
a set of coins.

A Python dict of outpoint -> coin taken through raw blocks, with or without
witnesses, by a parser of its own and `hashlib`: no code of the program. It
knows no rule but the bookkeeping. `connect` is `UpdateCoins` over a block:
a non-coinbase input removes the coin it names (which must be there) and
the record keeps it, every output adds one. `disconnect` is written from
Bitcoin Core 0.21's `DisconnectBlock` (validation.cpp) step by step:

    if (blockUndo.vtxundo.size() + 1 != block.vtx.size()) return FAILED
    for each transaction, last to first:
        for each output: SpendCoin; not there, or not equal to the block's
            own (amount, script, the block's height, coinbase) -> not clean
        if not the coinbase:
            if (txundo.vprevout.size() != tx.vin.size()) return FAILED
            for each input, last to first (ApplyTxInUndo):
                a coin already there -> not clean; AddCoin(the record's)
    return clean ? OK : UNCLEAN

Core runs those steps on a cache that `DisconnectTip` flushes to the view
on DISCONNECT_OK alone; here they run on a layer of changes over the dict,
written through on "ok" alone. What this does not implement raises.

Departures from Core, each on purpose:
- Core skips outputs whose script `IsUnspendable()` (they never enter its
  view); this repo's view holds every output, so every output is checked
  and removed.
- No BIP30 height exceptions (Core's two historic duplicate coinbases): a
  connect here never overwrites a coin.
- `ApplyTxInUndo`'s repair of a record without height metadata (written by
  versions before 0.15) is not here: a record is a list of whole coins.
- `disconnect` takes the block's height, which Core reads off its index.
"""

from __future__ import annotations

import hashlib
import struct
from collections import namedtuple
from typing import Dict, List, Optional, Set, Tuple

Outpoint = Tuple[bytes, int]
Coin = Tuple[int, bytes, int, bool]  # amount, scriptPubKey, height, coinbase
Undo = List[List[Coin]]  # CBlockUndo: a transaction after the coinbase, an input

NULL_OUTPOINT = (b"\x00" * 32, 0xFFFFFFFF)
_GONE = None  # a coin the layer of changes has removed

# What a view's `get` is asked with: the program's OutPoint by shape alone.
_Asked = namedtuple("_Asked", ("hash", "n"))


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
    """The transactions of a raw block: `txid`, `vin` (a list of outpoints)
    and `vout` (a list of (amount, scriptPubKey)). A transaction in the
    BIP 144 form (marker 0x00, flag 0x01, a witness stack an input) has the
    double SHA-256 of its form without them for its txid."""
    r = _Reader(raw)
    r.take(80)  # the header
    txs = []
    for _ in range(r.compact()):
        version = r.take(4)
        body = r.at
        n_in = r.compact()
        witness = n_in == 0
        if witness:
            if r.take(1) != b"\x01":
                raise ValueError("a witness marker with another flag than 1")
            body = r.at
            n_in = r.compact()
            if n_in == 0:
                raise ValueError("a transaction without inputs")
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
        body_end = r.at
        if witness:
            for _ in range(n_in):
                for _ in range(r.compact()):
                    r.take(r.compact())
        locktime = r.take(4)
        stripped = version + raw[body:body_end] + locktime
        txid = hashlib.sha256(hashlib.sha256(stripped).digest()).digest()
        txs.append({"txid": txid, "vin": vin, "vout": vout})
    if r.at != len(raw):
        raise ValueError("bytes after the last transaction")
    return txs


class _Layer:
    """Core's `CCoinsViewCache` over the view, as far as DisconnectBlock
    uses one: reads fall through, writes stay here until `flush`."""

    def __init__(self, base: Dict[Outpoint, Coin]):
        self.base = base
        self.changes: Dict[Outpoint, Optional[Coin]] = {}

    def get(self, op: Outpoint) -> Optional[Coin]:
        if op in self.changes:
            return self.changes[op]
        return self.base.get(op)

    def spend(self, op: Outpoint) -> Optional[Coin]:
        coin = self.get(op)
        if coin is not None:
            self.changes[op] = _GONE
        return coin

    def add(self, op: Outpoint, coin: Coin) -> None:
        self.changes[op] = coin

    def flush(self) -> None:
        for op, coin in self.changes.items():
            if coin is _GONE:
                del self.base[op]
            else:
                self.base[op] = coin


class ReorgRef:
    """`coins` after the blocks connected and disconnected so far, and
    every outpoint that was ever among them (`seen`)."""

    def __init__(self, coins):
        """`coins`: (txid, n, amount, height, coinbase, scriptPubKey), as
        the generators give them."""
        self.coins: Dict[Outpoint, Coin] = {
            (txid, n): (amount, spk, height, bool(cb))
            for txid, n, amount, height, cb, spk in coins
        }
        self.seen: Set[Outpoint] = set(self.coins)

    def connect(self, raw_block: bytes, height: int) -> Undo:
        """Apply the block and return its record: for each transaction
        after the coinbase, the coins its inputs removed, in input order."""
        undo: Undo = []
        for tx in parse_block(raw_block):
            coinbase = tx["vin"] == [NULL_OUTPOINT]
            if not coinbase:
                # KeyError: the block spends what is not there
                undo.append([self.coins.pop(op) for op in tx["vin"]])
            for n, (amount, spk) in enumerate(tx["vout"]):
                if (tx["txid"], n) in self.coins:
                    raise NotImplementedError("an output over a coin that is there (BIP30)")
                self.coins[(tx["txid"], n)] = (amount, spk, height, coinbase)
                self.seen.add((tx["txid"], n))
        return undo

    def disconnect(self, raw_block: bytes, undo: Undo, height: int) -> str:
        """DisconnectBlock, as the module's docstring sets it out: "ok",
        "unclean" or "failed"; the coins change on "ok" alone."""
        txs = parse_block(raw_block)
        if len(undo) + 1 != len(txs):
            return "failed"
        view = _Layer(self.coins)
        clean = True
        for i in reversed(range(len(txs))):
            tx = txs[i]
            coinbase = tx["vin"] == [NULL_OUTPOINT]
            for n, (amount, spk) in enumerate(tx["vout"]):
                coin = view.spend((tx["txid"], n))
                if coin is None or coin != (amount, spk, height, coinbase):
                    clean = False
            if i > 0:
                prevouts = undo[i - 1]
                if len(prevouts) != len(tx["vin"]):
                    return "failed"
                for j in reversed(range(len(tx["vin"]))):
                    if view.get(tx["vin"][j]) is not None:
                        clean = False
                    view.add(tx["vin"][j], prevouts[j])
        if not clean:
            return "unclean"
        view.flush()
        return "ok"

    def differences(self, view, untouched: int, limit: int = 5) -> List[str]:
        """What `view` (the program's, with `get(outpoint)` and `len`) holds
        otherwise than this reference, which knows all but `untouched` of
        its coins. Every coin here is looked up, and every outpoint that
        was ever here and is not now."""
        out: List[str] = []
        if len(view) != len(self.coins) + untouched:
            out.append(f"the view holds {len(view)} coins, the reference "
                       f"{len(self.coins)} + {untouched} untouched")
        for (txid, n), want in self.coins.items():
            coin = view.get(_Asked(txid, n))
            got = coin and (coin.out.value, coin.out.script_pubkey, coin.height, coin.coinbase)
            if got != want:
                out.append(f"coin {txid.hex()}:{n} is {got}, the reference has {want}")
        for txid, n in self.seen - set(self.coins):
            if view.get(_Asked(txid, n)) is not None:
                out.append(f"coin {txid.hex()}:{n} is gone here and is in the view")
        return out[:limit]
