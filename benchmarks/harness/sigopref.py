"""The plain reference of a block's sigop cost and of CHECKMULTISIG's key
walk, from raw transactions and the outputs they spend alone.

It shares nothing with the program's interpreters, parsers or accounting:
its own transaction reader, its own walk over a script's opcodes, Core's
counting rules written out (`script.cpp` GetSigOpCount, `interpreter.cpp`
CountWitnessSigOps, `tx_verify.cpp` GetTransactionSigOpCost) and Core's
top-down pairing of signatures with keys (`interpreter.cpp:1177-1205`)
over a pairing oracle the caller gives. With `ec_pairing` the oracle is
this benchmark's own curve code over this file's own BIP 143 digest, so a
verdict from here owes the program nothing.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

OP_PUSHDATA1, OP_PUSHDATA2, OP_PUSHDATA4 = 0x4C, 0x4D, 0x4E
OP_1, OP_16 = 0x51, 0x60
OP_CHECKSIG, OP_CHECKSIGVERIFY = 0xAC, 0xAD
OP_CHECKMULTISIG, OP_CHECKMULTISIGVERIFY = 0xAE, 0xAF
MAX_PUBKEYS_PER_MULTISIG = 20
WITNESS_SCALE_FACTOR = 4

Output = Tuple[int, bytes]  # (amount, scriptPubKey)


class TxIn(NamedTuple):
    prev_hash: bytes
    prev_n: int
    script_sig: bytes
    sequence: int
    witness: List[bytes]


class Tx(NamedTuple):
    version: int
    vin: List[TxIn]
    vout: List[Output]
    locktime: int


class _Reader:
    def __init__(self, raw: bytes):
        self.raw, self.at = raw, 0

    def take(self, n: int) -> bytes:
        if self.at + n > len(self.raw):
            raise ValueError("transaction ends early")
        out = self.raw[self.at : self.at + n]
        self.at += n
        return out

    def u(self, fmt: str) -> int:
        return struct.unpack("<" + fmt, self.take(struct.calcsize(fmt)))[0]

    def varint(self) -> int:
        first = self.u("B")
        return first if first < 0xFD else self.u({0xFD: "H", 0xFE: "I", 0xFF: "Q"}[first])

    def varbytes(self) -> bytes:
        return self.take(self.varint())


def parse_tx(raw: bytes) -> Tx:
    r = _Reader(raw)
    version = r.u("i")
    n_in = r.varint()
    segwit = n_in == 0
    if segwit:
        if r.u("B") != 1:
            raise ValueError("unknown transaction flag")
        n_in = r.varint()
    ins = [(r.take(32), r.u("I"), r.varbytes(), r.u("I")) for _ in range(n_in)]
    vout = [(r.u("q"), r.varbytes()) for _ in range(r.varint())]
    witnesses = [[r.varbytes() for _ in range(r.varint())] if segwit else [] for _ in ins]
    locktime = r.u("I")
    if r.at != len(raw):
        raise ValueError("bytes after the transaction")
    return Tx(version, [TxIn(*i, w) for i, w in zip(ins, witnesses)], vout, locktime)


def script_ops(script: bytes):
    """(opcode, pushed data or None) of each operation; stops, as Core's
    GetOp does, where a push runs past the end."""
    at = 0
    while at < len(script):
        op = script[at]
        at += 1
        if op > OP_PUSHDATA4:
            yield op, None
            continue
        if op < OP_PUSHDATA1:
            size = op
        else:
            width = {OP_PUSHDATA1: 1, OP_PUSHDATA2: 2, OP_PUSHDATA4: 4}[op]
            if at + width > len(script):
                return
            size = int.from_bytes(script[at : at + width], "little")
            at += width
        if at + size > len(script):
            return
        yield op, script[at : at + size]
        at += size


def script_sigops(script: bytes, accurate: bool) -> int:
    """GetSigOpCount: a CHECKSIG is one; a CHECKMULTISIG is the number its
    preceding OP_1..OP_16 names when `accurate`, else 20, the most it can take."""
    count, last = 0, 0xFF
    for op, _data in script_ops(script):
        if op in (OP_CHECKSIG, OP_CHECKSIGVERIFY):
            count += 1
        elif op in (OP_CHECKMULTISIG, OP_CHECKMULTISIGVERIFY):
            count += last - OP_1 + 1 if accurate and OP_1 <= last <= OP_16 else MAX_PUBKEYS_PER_MULTISIG
        last = op
    return count


def _is_p2sh(spk: bytes) -> bool:
    return len(spk) == 23 and spk[0] == 0xA9 and spk[1] == 0x14 and spk[22] == 0x87


def _witness_program(script: bytes) -> Optional[Tuple[int, bytes]]:
    if not 4 <= len(script) <= 42 or script[1] + 2 != len(script):
        return None
    if script[0] != 0 and not OP_1 <= script[0] <= OP_16:
        return None
    return (0 if script[0] == 0 else script[0] - OP_1 + 1), script[2:]


def _last_push(script_sig: bytes) -> Optional[bytes]:
    """The last item a push-only scriptSig leaves, else None."""
    data = None
    for op, pushed in script_ops(script_sig):
        if op > OP_16:
            return None
        data = pushed if pushed is not None else b""
    return data


def _witness_sigops(version: int, program: bytes, witness: Sequence[bytes]) -> int:
    if version != 0:
        return 0
    if len(program) == 20:
        return 1
    if len(program) == 32 and witness:
        return script_sigops(witness[-1], accurate=True)
    return 0


def tx_sigop_cost(tx: Tx, spent: Sequence[Output]) -> int:
    """GetTransactionSigOpCost with P2SH and WITNESS on: legacy x 4, the
    redeem script of a P2SH spend x 4, a witness program's own count x 1.
    A coinbase is passed with no `spent`."""
    legacy = sum(script_sigops(i.script_sig, False) for i in tx.vin)
    legacy += sum(script_sigops(spk, False) for _, spk in tx.vout)
    cost = legacy * WITNESS_SCALE_FACTOR
    if not spent:
        return cost
    if len(spent) != len(tx.vin):
        raise ValueError("one spent output an input")
    for txin, (_, spk) in zip(tx.vin, spent):
        redeem = _last_push(txin.script_sig) if _is_p2sh(spk) else None
        if redeem is not None:
            cost += script_sigops(redeem, True) * WITNESS_SCALE_FACTOR
        program = _witness_program(spk)
        if program is None and redeem is not None:
            program = _witness_program(redeem)
        if program is not None:
            cost += _witness_sigops(*program, txin.witness)
    return cost


def block_sigop_cost(coinbase: Tx, spends: Sequence[Tuple[Tx, Sequence[Output]]]) -> int:
    return tx_sigop_cost(coinbase, []) + sum(tx_sigop_cost(tx, outs) for tx, outs in spends)


# -- CHECKMULTISIG's key walk ----------------------------------------------------

def parse_bare_multisig(script: bytes) -> Tuple[int, List[bytes]]:
    """(m, keys in push order) of `m <keys> n CHECKMULTISIG` and nothing else."""
    def number(op, data):
        if data is None and OP_1 <= op <= OP_16:
            return op - OP_1 + 1
        if data is not None and len(data) == 1 and 16 < data[0] < 0x80:
            return data[0]
        raise ValueError("not a small positive number")

    ops = list(script_ops(script))
    if len(ops) < 4 or ops[-1] != (OP_CHECKMULTISIG, None):
        raise ValueError("not a bare CHECKMULTISIG script")
    m, n = number(*ops[0]), number(*ops[-2])
    keys = [data for _op, data in ops[1:-2]]
    if any(k is None for k in keys) or len(keys) != n or not 1 <= m <= n <= MAX_PUBKEYS_PER_MULTISIG:
        raise ValueError("key list does not match its count")
    return m, keys


Pairing = Callable[[bytes, bytes], bool]  # (signature with hashtype, key) -> verifies


def multisig_walk(m: int, keys: Sequence[bytes], sigs: Sequence[bytes],
                  pairing: Pairing) -> Tuple[List[Tuple[int, int]], bool]:
    """Core's walk: from the last-pushed signature and the last-pushed key
    down; a pairing that verifies consumes both, one that fails consumes
    the key; the script fails as soon as fewer keys than signatures are
    left. Returns the (signature, key) positions tried, in push order from
    0, in the order tried, and the verdict."""
    if len(sigs) != m:
        raise ValueError("one signature for each of the m")
    tried: List[Tuple[int, int]] = []
    isig, ikey = len(sigs) - 1, len(keys) - 1
    while isig >= 0:
        if isig > ikey:
            return tried, False
        tried.append((isig, ikey))
        if pairing(sigs[isig], keys[ikey]):
            isig -= 1
        ikey -= 1
    return tried, True


def _sha256d(b: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(b).digest()).digest()


def _varbytes(b: bytes) -> bytes:
    n = len(b)
    head = bytes([n]) if n < 0xFD else b"\xfd" + struct.pack("<H", n) if n <= 0xFFFF \
        else b"\xfe" + struct.pack("<I", n)
    return head + b


def bip143_digest_all(tx: Tx, index: int, script_code: bytes, amount: int) -> bytes:
    """The BIP 143 digest of input `index` under SIGHASH_ALL."""
    txin = tx.vin[index]
    prevouts = b"".join(i.prev_hash + struct.pack("<I", i.prev_n) for i in tx.vin)
    sequences = b"".join(struct.pack("<I", i.sequence) for i in tx.vin)
    outputs = b"".join(struct.pack("<q", v) + _varbytes(spk) for v, spk in tx.vout)
    return _sha256d(
        struct.pack("<i", tx.version) + _sha256d(prevouts) + _sha256d(sequences)
        + txin.prev_hash + struct.pack("<I", txin.prev_n) + _varbytes(script_code)
        + struct.pack("<q", amount) + struct.pack("<I", txin.sequence)
        + _sha256d(outputs) + struct.pack("<I", tx.locktime) + struct.pack("<I", 1)
    )


def ec_pairing(tx: Tx, index: int, script: bytes, amount: int) -> Pairing:
    """The pairing oracle of one P2WSH input by this benchmark's own curve
    code: a SIGHASH_ALL signature against a compressed key."""
    from .ecverify import verify_ecdsa

    digest = bip143_digest_all(tx, index, script, amount)

    def pairing(sig: bytes, key: bytes) -> bool:
        return len(sig) > 1 and sig[-1] == 1 and verify_ecdsa(key, sig[:-1], digest)

    return pairing


def p2wsh_multisig_input(tx: Tx, index: int, spent: Output,
                         pairing: Optional[Pairing] = None) -> Tuple[List[Tuple[int, int]], bool]:
    """Walk one input whose spent output is a P2WSH of a bare multisig and
    whose witness is `<> <sigs> <script>`: (pairings tried, verdict), with
    `ec_pairing` unless another oracle is given."""
    amount, spk = spent
    witness = tx.vin[index].witness
    if len(witness) < 3 or witness[0] != b"":
        raise ValueError("not a `<> <sigs> <script>` witness")
    script = witness[-1]
    if spk != b"\x00\x20" + hashlib.sha256(script).digest():
        return [], False
    m, keys = parse_bare_multisig(script)
    return multisig_walk(m, keys, witness[1:-1],
                         pairing or ec_pairing(tx, index, script, amount))
