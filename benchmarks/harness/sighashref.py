"""The plain reference of a legacy input: Core's pre-BIP143 `SignatureHash`
and the scripts that reach it, from a raw transaction and the outputs it
spends alone.

`SignatureHash` under `SigVersion::BASE` (Core 0.21 `script/interpreter.cpp`)
hashes, for every signature check, the WHOLE transaction as
`CTransactionSignatureSerializer` writes it: every other input's script
blanked, this input's replaced by the script code, inputs and outputs cut
down by the hash type. It is written out here straight from that
description, with `hashlib`, a fresh preimage a digest and no midstate, so
that the length of what was hashed is this file's own count. Around it, as
much of `EvalScript` as a P2PKH spend, a bare `<key> CHECKSIG` and the two
oddities of the script code need: the pushes, OP_DUP, OP_HASH160,
OP_EQUALVERIFY, OP_DROP, OP_CODESEPARATOR, OP_CHECKSIG(VERIFY). The flags
are those of mainnet at height 364,292: P2SH and DERSIG, nothing else (no
NULLFAIL, no STRICTENC, no LOW_S, no MINIMALDATA, no WITNESS).

It imports nothing of the program: the transaction reader is
`sigopref.py`'s, the DER rule `msigref.py`'s, the curve `ecverify.py`'s
plain integers. What it does not implement raises `Unsupported`; it never
passes: any other opcode, a P2SH or witness-program output, a script or an
element over the size limits. Nothing here runs inside a measured window.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple, Union

from . import sigopref
from .ec import P
from .ecverify import verify_ecdsa
from .msigref import MAX_SCRIPT_ELEMENT_SIZE, MAX_SCRIPT_SIZE, Unsupported, valid_der

SIGHASH_ALL, SIGHASH_NONE, SIGHASH_SINGLE, SIGHASH_ANYONECANPAY = 1, 2, 3, 0x80

OP_PUSHDATA1, OP_PUSHDATA2, OP_PUSHDATA4, OP_1, OP_16 = 0x4C, 0x4D, 0x4E, 0x51, 0x60
OP_DROP, OP_DUP, OP_EQUALVERIFY = 0x75, 0x76, 0x88
OP_HASH160, OP_CODESEPARATOR = 0xA9, 0xAB
OP_CHECKSIG, OP_CHECKSIGVERIFY = 0xAC, 0xAD

ONE = b"\x01" + b"\x00" * 31  # the digest of SIGHASH_SINGLE with no matching output


class Verdict(NamedTuple):
    ok: bool
    error: str  # the ScriptError's name, "OK" for a passing input
    preimage_bytes: int  # bytes hashed for this input's digests, by this file's count


class _Ends(Exception):
    """The script ends here with this ScriptError."""


# -- the script code ---------------------------------------------------------------

def compact_size(n: int) -> bytes:
    """`WriteCompactSize`."""
    if n < 0xFD:
        return bytes([n])
    if n <= 0xFFFF:
        return b"\xfd" + struct.pack("<H", n)
    if n <= 0xFFFFFFFF:
        return b"\xfe" + struct.pack("<I", n)
    return b"\xff" + struct.pack("<Q", n)


def _next_op(script: bytes, at: int) -> Optional[Tuple[int, int, Optional[bytes]]]:
    """`GetOp` at `at`: (where the next operation starts, opcode, pushed
    data or None); None at the end and where a push runs past it."""
    if at >= len(script):
        return None
    op = script[at]
    at += 1
    if op > OP_PUSHDATA4:
        return at, op, None
    size = op
    if op >= OP_PUSHDATA1:
        width = {OP_PUSHDATA1: 1, OP_PUSHDATA2: 2, OP_PUSHDATA4: 4}[op]
        if at + width > len(script):
            return None
        size = int.from_bytes(script[at : at + width], "little")
        at += width
    if at + size > len(script):
        return None
    return at + size, op, script[at : at + size]


def _ops(script: bytes) -> Iterator[Tuple[int, int, int, Optional[bytes]]]:
    """(start, end, opcode, pushed data or None) of each operation, as far
    as `GetOp` reads."""
    at = 0
    while (step := _next_op(script, at)) is not None:
        yield (at, *step)
        at = step[0]


def push(data: bytes) -> bytes:
    """`CScript() << data`: the shortest push by length alone (an empty
    vector is OP_0)."""
    n = len(data)
    if n < OP_PUSHDATA1:
        return bytes([n]) + data
    if n <= 0xFF:
        return bytes([OP_PUSHDATA1, n]) + data
    if n <= 0xFFFF:
        return bytes([OP_PUSHDATA2]) + struct.pack("<H", n) + data
    return bytes([OP_PUSHDATA4]) + struct.pack("<I", n) + data


def find_and_delete(script: bytes, sig: bytes) -> bytes:
    """`FindAndDelete(scriptCode, CScript() << sig)`, Core's loop as it
    stands: at every place an operation starts, and once more where `GetOp`
    stops, cut out the signature's push as often as it repeats there. An
    occurrence inside another push is not one."""
    needle = push(sig)
    kept, found, at, copied_to = [], 0, 0, 0
    while True:
        kept.append(script[copied_to:at])
        while script[at : at + len(needle)] == needle:
            at += len(needle)
            found += 1
        copied_to = at
        step = _next_op(script, at)
        if step is None:
            break
        at = step[0]
    return b"".join(kept) + script[copied_to:] if found else script


def serialize_script_code(script_code: bytes) -> bytes:
    """`SerializeScriptCode`: the script code behind its length, with every
    OP_CODESEPARATOR that is an operation of its own left out."""
    cuts = [start for start, _end, op, _data in _ops(script_code) if op == OP_CODESEPARATOR]
    kept, at = [], 0
    for cut in cuts:
        kept.append(script_code[at:cut])
        at = cut + 1
    kept.append(script_code[at:])
    body = b"".join(kept)
    return compact_size(len(body)) + body


# -- SignatureHash -----------------------------------------------------------------

def preimage(tx: sigopref.Tx, index: int, script_code: bytes, hash_type: int) -> Optional[bytes]:
    """What `SignatureHash` hashes for input `index`: the transaction as
    `CTransactionSignatureSerializer` writes it and the hash type behind
    it. None where Core hashes nothing: SIGHASH_SINGLE with no output at
    `index`, whose digest is the number one."""
    anyone = bool(hash_type & SIGHASH_ANYONECANPAY)
    single = hash_type & 0x1F == SIGHASH_SINGLE
    none = hash_type & 0x1F == SIGHASH_NONE
    if single and index >= len(tx.vout):
        return None

    def one_input(i: int) -> bytes:
        txin = tx.vin[i]
        script = serialize_script_code(script_code) if i == index else b"\x00"
        sequence = 0 if i != index and (single or none) else txin.sequence
        return txin.prev_hash + struct.pack("<I", txin.prev_n) + script + struct.pack("<I", sequence)

    def one_output(i: int) -> bytes:
        if single and i != index:
            return struct.pack("<q", -1) + b"\x00"  # CTxOut(): value -1, no script
        value, spk = tx.vout[i]
        return struct.pack("<q", value) + compact_size(len(spk)) + spk

    inputs = [index] if anyone else range(len(tx.vin))
    n_outputs = 0 if none else index + 1 if single else len(tx.vout)
    return b"".join([
        struct.pack("<i", tx.version),
        compact_size(len(inputs)), *[one_input(i) for i in inputs],
        compact_size(n_outputs), *[one_output(i) for i in range(n_outputs)],
        struct.pack("<I", tx.locktime),
        struct.pack("<i", hash_type),
    ])


def signature_hash(tx: sigopref.Tx, index: int, script_code: bytes,
                   hash_type: int) -> Tuple[bytes, int]:
    """(digest, bytes of the preimage hashed for it)."""
    data = preimage(tx, index, script_code, hash_type)
    if data is None:
        return ONE, 0
    return hashlib.sha256(hashlib.sha256(data).digest()).digest(), len(data)


# -- the curve ---------------------------------------------------------------------

def _compressed(pub: bytes) -> Optional[bytes]:
    """`CPubKey::IsValid` and `secp256k1_ec_pubkey_parse`: a 33-byte key as
    it stands; a 65-byte key (04, or the hybrid 06 / 07 whose last bit
    names y's parity) in its compressed form where the point is on the
    curve; anything else verifies against nothing."""
    if len(pub) == 33 and pub[0] in (2, 3):
        return pub
    if len(pub) != 65 or pub[0] not in (4, 6, 7):
        return None
    x, y = int.from_bytes(pub[1:33], "big"), int.from_bytes(pub[33:], "big")
    if x >= P or y >= P or (y * y - x * x * x - 7) % P:
        return None
    if pub[0] != 4 and (y & 1) != (pub[0] & 1):
        return None
    return bytes([2 + (y & 1)]) + pub[1:33]


# -- EvalScript, as far as these inputs go -----------------------------------------

def _truth(item: bytes) -> bool:
    """CastToBool: any byte set, but for a negative zero."""
    return any(item[:-1]) or (bool(item) and item[-1] not in (0, 0x80))


class _Machine:
    def __init__(self, tx: sigopref.Tx, index: int):
        self.tx, self.index = tx, index
        self.stack: List[bytes] = []
        self.hashed = 0

    def _pop(self, n: int) -> List[bytes]:
        if len(self.stack) < n:
            raise _Ends("INVALID_STACK_OPERATION")
        taken = self.stack[-n:]
        del self.stack[-n:]
        return taken

    def checksig(self, sig: bytes, key: bytes, script_code: bytes) -> bool:
        script_code = find_and_delete(script_code, sig)
        if sig and not valid_der(sig):  # DERSIG; an empty signature passes and fails the check
            raise _Ends("SIG_DER")
        point = _compressed(key)
        if not sig or point is None:
            return False
        digest, hashed = signature_hash(self.tx, self.index, script_code, sig[-1])
        self.hashed += hashed
        return verify_ecdsa(point, sig[:-1], digest)

    def run(self, script: bytes) -> None:
        if len(script) > MAX_SCRIPT_SIZE:
            raise Unsupported("a script over the size limit")
        code_from = 0
        reached = 0
        for _start, end, op, data in _ops(script):
            reached = end
            if data is not None:
                if len(data) > MAX_SCRIPT_ELEMENT_SIZE:
                    raise Unsupported("an element over the size limit")
                self.stack.append(data)
            elif OP_1 <= op <= OP_16:
                self.stack.append(bytes([op - OP_1 + 1]))
            elif op == OP_DUP:
                top, = self._pop(1)
                self.stack += [top, top]
            elif op == OP_DROP:
                self._pop(1)
            elif op == OP_HASH160:
                top, = self._pop(1)
                self.stack.append(hashlib.new("ripemd160", hashlib.sha256(top).digest()).digest())
            elif op == OP_EQUALVERIFY:
                a, b = self._pop(2)
                if a != b:
                    raise _Ends("EQUALVERIFY")
            elif op == OP_CODESEPARATOR:
                code_from = end
            elif op in (OP_CHECKSIG, OP_CHECKSIGVERIFY):
                sig, key = self._pop(2)
                ok = self.checksig(sig, key, script[code_from:])
                if op == OP_CHECKSIGVERIFY:
                    if not ok:
                        raise _Ends("CHECKSIGVERIFY")
                else:
                    self.stack.append(b"\x01" if ok else b"")
            else:
                raise Unsupported(f"opcode {op:#x}")
        if reached != len(script):
            raise _Ends("BAD_OPCODE")  # a push that runs past the end


def verify_input(tx: Union[bytes, sigopref.Tx], index: int,
                 spent: Sequence[sigopref.Output]) -> Verdict:
    """Input `index` of `tx` (raw bytes or parsed), which spends
    `spent[index]`: `VerifyScript` under P2SH and DERSIG for an output that
    is neither P2SH nor a witness program."""
    if isinstance(tx, (bytes, bytearray)):
        tx = sigopref.parse_tx(bytes(tx))
    _amount, spk = spent[index]
    txin = tx.vin[index]
    if sigopref._is_p2sh(spk) or sigopref._witness_program(spk) is not None or txin.witness:
        raise Unsupported("a P2SH or witness spend")
    m = _Machine(tx, index)
    try:
        m.run(txin.script_sig)
        m.run(spk)
    except _Ends as e:
        return Verdict(False, str(e), m.hashed)
    ok = bool(m.stack) and _truth(m.stack[-1])
    return Verdict(ok, "OK" if ok else "EVAL_FALSE", m.hashed)
