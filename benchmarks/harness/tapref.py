"""The plain reference of a taproot-era input: BIP 341 and BIP 342 written
out, from a raw transaction and the outputs it spends alone.

It shares nothing with the program's interpreters, sighash code, curve code
or native core: the transaction reader is `sigopref.py`'s, the tagged
hashes, the BIP 341 `SigMsg` (key path and tapscript), the commitment check
and the walk over a leaf are this file's, and the curve is
`schnorrverify.py`'s and `ecverify.py`'s plain integers. A P2WPKH input
goes through `sigopref.bip143_digest_all` and `ecverify.verify_ecdsa` as
they stand. `verify_input` returns the verdict, the name of Core's
`ScriptError` and the curve checks it made, by kind.

Where this departs from the BIPs, it says so where it does, and what it
does not implement raises `Unsupported`; it never passes:

- the annex (BIP 341 leaves it without meaning; no traffic here has one);
- in a leaf, every opcode but the pushes, OP_0-OP_16, OP_CHECKSIG,
  OP_CHECKSIGVERIFY, OP_CHECKSIGADD, OP_NUMEQUAL, OP_EQUAL, the OP_SUCCESSx
  (which pass, as BIP 342 says) and OP_CHECKMULTISIG(VERIFY) (which fail,
  as it says); numbers longer than four bytes;
- spent outputs other than P2TR and P2WPKH; a P2WPKH signature that is not
  SIGHASH_ALL or a key that is not compressed. Of Core's DER rules only the
  structure is held (`ecverify.parse_der`).

Core's error names and its order of checks (`interpreter.cpp`
`VerifyWitnessProgram`, `EvalChecksigTapscript`) are followed where the
BIPs leave an order open, since the name is part of what is compared.
Nothing here runs inside a measured window.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from . import sigopref
from .ecverify import parse_der, verify_ecdsa
from .sigopref import OP_PUSHDATA1, _varbytes
from .schnorrverify import tweak_add_check, verify_schnorr

LEAF_TAPSCRIPT = 0xC0
LEAF_MASK = 0xFE
ANNEX_TAG = 0x50
CONTROL_BASE, CONTROL_NODE, CONTROL_MAX_NODES = 33, 32, 128
VALIDATION_WEIGHT_PER_SIGOP_PASSED = 50  # interpreter.h
VALIDATION_WEIGHT_OFFSET = 50
MAX_STACK_SIZE, MAX_ELEMENT_SIZE = 1000, 520

OP_PUSHDATA4, OP_1, OP_16 = 0x4E, 0x51, 0x60
OP_EQUAL, OP_NUMEQUAL = 0x87, 0x9C
OP_CHECKSIG, OP_CHECKSIGVERIFY = 0xAC, 0xAD
OP_CHECKMULTISIG, OP_CHECKMULTISIGVERIFY = 0xAE, 0xAF
OP_CHECKSIGADD = 0xBA
# BIP 342: 80, 98, 126-129, 131-134, 137-138, 141-142, 149-153, 187-254.
OP_SUCCESS = frozenset(
    [80, 98, *range(126, 130), *range(131, 135), 137, 138, 141, 142,
     *range(149, 154), *range(187, 255)]
)

KINDS = ("ecdsa", "schnorr", "tweak")


class Unsupported(Exception):
    """The input needs a rule this reference does not implement."""


class Verdict(NamedTuple):
    ok: bool
    error: str  # the name of Core's ScriptError; "OK" for a pass
    checks: Dict[str, int]  # curve checks made, by kind


class _Fail(Exception):
    """Ends an input's validation with a ScriptError's name."""


def tagged(tag: str, data: bytes) -> bytes:
    t = hashlib.sha256(tag.encode()).digest()
    return hashlib.sha256(t + t + data).digest()


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def tapleaf_hash(leaf_version: int, script: bytes) -> bytes:
    return tagged("TapLeaf", bytes([leaf_version]) + _varbytes(script))


def tapbranch_hash(a: bytes, b: bytes) -> bytes:
    """BIP 341: the two children in lexicographic order."""
    return tagged("TapBranch", a + b if a < b else b + a)


def taptweak_hash(internal32: bytes, root: bytes) -> bytes:
    return tagged("TapTweak", internal32 + root)


class Spend:
    """One transaction and the outputs it spends, read once; `verify(i)`
    validates input i."""

    def __init__(self, raw: bytes, outs: Sequence[sigopref.Output]):
        self.tx = sigopref.parse_tx(raw)
        self.outs = list(outs)
        if len(self.outs) != len(self.tx.vin):
            raise ValueError("one spent output an input")
        self._shared: Optional[Tuple[bytes, bytes, bytes, bytes, bytes]] = None

    # -- BIP 341 "Common signature message" ---------------------------------

    def _hashes(self):
        if self._shared is None:
            tx = self.tx
            self._shared = (
                _sha(b"".join(i.prev_hash + struct.pack("<I", i.prev_n) for i in tx.vin)),
                _sha(b"".join(struct.pack("<q", a) for a, _ in self.outs)),
                _sha(b"".join(_varbytes(spk) for _, spk in self.outs)),
                _sha(b"".join(struct.pack("<I", i.sequence) for i in tx.vin)),
                _sha(b"".join(struct.pack("<q", v) + _varbytes(spk) for v, spk in tx.vout)),
            )
        return self._shared

    def sighash(self, index: int, hash_type: int, leaf: Optional[bytes]) -> Optional[bytes]:
        """The digest a Schnorr signature of input `index` commits to:
        `leaf` None for the key path (ext_flag 0), else the tapleaf hash
        (ext_flag 1, key_version 0, codesep_pos 0xFFFFFFFF: this reference
        walks no OP_CODESEPARATOR). None where BIP 341 fails the input: an
        undefined hash type, SIGHASH_SINGLE without its output."""
        if hash_type not in (0x00, 0x01, 0x02, 0x03, 0x81, 0x82, 0x83):
            return None
        tx, txin = self.tx, self.tx.vin[index]
        prevouts, amounts, spks, sequences, outputs = self._hashes()
        anyone = hash_type & 0x80
        out_type = hash_type & 3  # 0 (default) and 1 commit to every output
        msg = bytes([hash_type]) + struct.pack("<iI", tx.version, tx.locktime)
        if not anyone:
            msg += prevouts + amounts + spks + sequences
        if out_type not in (2, 3):
            msg += outputs
        msg += bytes([2 if leaf is not None else 0])  # spend_type; no annex here
        if anyone:
            amount, spk = self.outs[index]
            msg += (txin.prev_hash + struct.pack("<I", txin.prev_n) + struct.pack("<q", amount)
                    + _varbytes(spk) + struct.pack("<I", txin.sequence))
        else:
            msg += struct.pack("<I", index)
        if out_type == 3:
            if index >= len(tx.vout):
                return None
            value, spk = tx.vout[index]
            msg += _sha(struct.pack("<q", value) + _varbytes(spk))
        if leaf is not None:
            msg += leaf + b"\x00" + struct.pack("<I", 0xFFFFFFFF)
        return tagged("TapSighash", b"\x00" + msg)

    # -- the checks ----------------------------------------------------------

    def _schnorr(self, index: int, sig: bytes, key32: bytes, leaf: Optional[bytes],
                 checks: Dict[str, int]) -> None:
        """BIP 341 "Signature validation rules" for a non-empty signature."""
        if len(sig) not in (64, 65):
            raise _Fail("SCHNORR_SIG_SIZE")
        hash_type = 0
        if len(sig) == 65:
            hash_type, sig = sig[64], sig[:64]
            if hash_type == 0:
                raise _Fail("SCHNORR_SIG_HASHTYPE")
        digest = self.sighash(index, hash_type, leaf)
        if digest is None:
            raise _Fail("SCHNORR_SIG_HASHTYPE")
        checks["schnorr"] += 1
        if not verify_schnorr(key32, sig, digest):
            raise _Fail("SCHNORR_SIG")

    def _p2wpkh(self, index: int, program: bytes, checks: Dict[str, int]) -> None:
        witness = self.tx.vin[index].witness
        if len(witness) != 2:
            raise _Fail("WITNESS_PROGRAM_MISMATCH")
        sig, pub = witness
        if hashlib.new("ripemd160", _sha(pub)).digest() != program:
            raise _Fail("EQUALVERIFY")
        if not sig:
            raise _Fail("EVAL_FALSE")
        if parse_der(sig[:-1]) is None:
            raise _Fail("SIG_DER")
        if sig[-1] != 1 or len(pub) != 33:
            raise Unsupported("a P2WPKH spend that is not SIGHASH_ALL by a compressed key")
        code = b"\x76\xa9\x14" + program + b"\x88\xac"
        digest = sigopref.bip143_digest_all(self.tx, index, code, self.outs[index][0])
        checks["ecdsa"] += 1
        if not verify_ecdsa(pub, sig[:-1], digest):
            raise _Fail("EVAL_FALSE")

    def _p2tr(self, index: int, program: bytes, checks: Dict[str, int],
              discourage_unknown_keys: bool) -> None:
        witness = self.tx.vin[index].witness
        stack = list(witness)
        if not stack:
            raise _Fail("WITNESS_PROGRAM_WITNESS_EMPTY")
        if len(stack) >= 2 and stack[-1][:1] == bytes([ANNEX_TAG]):
            raise Unsupported("an annex")
        if len(stack) == 1:  # key path
            self._schnorr(index, stack[0], program, None, checks)
            return
        control, script = stack.pop(), stack.pop()
        nodes, rest = divmod(len(control) - CONTROL_BASE, CONTROL_NODE)
        if len(control) < CONTROL_BASE or rest or nodes > CONTROL_MAX_NODES:
            raise _Fail("TAPROOT_WRONG_CONTROL_SIZE")
        leaf_version, parity = control[0] & LEAF_MASK, control[0] & 1
        internal = control[1:CONTROL_BASE]
        leaf = k = tapleaf_hash(leaf_version, script)
        for j in range(nodes):
            at = CONTROL_BASE + CONTROL_NODE * j
            k = tapbranch_hash(k, control[at : at + CONTROL_NODE])
        checks["tweak"] += 1
        if not tweak_add_check(program, parity, internal, taptweak_hash(internal, k)):
            raise _Fail("WITNESS_PROGRAM_MISMATCH")
        if leaf_version != LEAF_TAPSCRIPT:
            return  # BIP 341: an unknown leaf version is left to a later fork
        budget = _witness_size(witness) + VALIDATION_WEIGHT_OFFSET
        self._tapscript(index, script, stack, leaf, budget, checks, discourage_unknown_keys)

    def _tapscript(self, index: int, script: bytes, stack: List[bytes], leaf: bytes,
                   budget: int, checks: Dict[str, int], discourage_unknown_keys: bool) -> None:
        ops = []
        for op, data in _decode(script):
            if op in OP_SUCCESS:
                return  # BIP 342: an OP_SUCCESSx makes the script pass, unexecuted
            ops.append((op, data))
        if len(stack) > MAX_STACK_SIZE:
            raise _Fail("STACK_SIZE")
        if any(len(item) > MAX_ELEMENT_SIZE for item in stack):
            raise _Fail("PUSH_SIZE")

        def pop() -> bytes:
            if not stack:
                raise _Fail("INVALID_STACK_OPERATION")
            return stack.pop()

        def checksig(sig: bytes, key: bytes) -> bool:
            """BIP 342 "Rules for signature opcodes", in Core's order."""
            nonlocal budget
            if sig:
                budget -= VALIDATION_WEIGHT_PER_SIGOP_PASSED
                if budget < 0:
                    raise _Fail("TAPSCRIPT_VALIDATION_WEIGHT")
            if not key:
                raise _Fail("PUBKEYTYPE")
            if len(key) == 32:
                if sig:
                    self._schnorr(index, sig, key, leaf, checks)
            elif discourage_unknown_keys:  # a key of an unknown type: no check at all
                raise _Fail("DISCOURAGE_UPGRADABLE_PUBKEYTYPE")
            return bool(sig)

        for op, data in ops:
            if data is not None:
                if len(data) > MAX_ELEMENT_SIZE:
                    raise _Fail("PUSH_SIZE")
                stack.append(data)
            elif OP_1 <= op <= OP_16:
                stack.append(bytes([op - OP_1 + 1]))
            elif op in (OP_CHECKSIG, OP_CHECKSIGVERIFY):
                if len(stack) < 2:
                    raise _Fail("INVALID_STACK_OPERATION")
                key, sig = pop(), pop()
                passed = checksig(sig, key)
                if op == OP_CHECKSIGVERIFY and not passed:
                    raise _Fail("CHECKSIGVERIFY")
                if op == OP_CHECKSIG:
                    stack.append(b"\x01" if passed else b"")
            elif op == OP_CHECKSIGADD:
                if len(stack) < 3:
                    raise _Fail("INVALID_STACK_OPERATION")
                key, n, sig = pop(), _number(pop()), pop()
                stack.append(_encode_number(n + (1 if checksig(sig, key) else 0)))
            elif op in (OP_CHECKMULTISIG, OP_CHECKMULTISIGVERIFY):
                raise _Fail("TAPSCRIPT_CHECKMULTISIG")
            elif op == OP_NUMEQUAL:
                if len(stack) < 2:
                    raise _Fail("INVALID_STACK_OPERATION")
                b, a = _number(pop()), _number(pop())
                stack.append(b"\x01" if a == b else b"")
            elif op == OP_EQUAL:
                if len(stack) < 2:
                    raise _Fail("INVALID_STACK_OPERATION")
                b, a = pop(), pop()
                stack.append(b"\x01" if a == b else b"")
            else:
                raise Unsupported(f"opcode 0x{op:02x} in a leaf")
            if len(stack) > MAX_STACK_SIZE:
                raise _Fail("STACK_SIZE")
        if len(stack) != 1:
            raise _Fail("CLEANSTACK")
        if not _truth(stack[0]):
            raise _Fail("EVAL_FALSE")

    def verify(self, index: int, discourage_unknown_keys: bool = False) -> Verdict:
        """Input `index` as consensus validates it at a height where taproot
        is active. `discourage_unknown_keys` is Core's policy flag
        DISCOURAGE_UPGRADABLE_PUBKEYTYPE, no rule of BIP 342."""
        if not 0 <= index < len(self.tx.vin):
            raise ValueError("no such input")
        checks = dict.fromkeys(KINDS, 0)
        _, spk = self.outs[index]
        try:
            if self.tx.vin[index].script_sig:
                raise _Fail("WITNESS_MALLEATED")
            if len(spk) == 34 and spk[:2] == b"\x51\x20":
                self._p2tr(index, spk[2:], checks, discourage_unknown_keys)
            elif len(spk) == 22 and spk[:2] == b"\x00\x14":
                self._p2wpkh(index, spk[2:], checks)
            else:
                raise Unsupported(f"a spent output that is neither P2TR nor P2WPKH: {spk.hex()}")
        except _Fail as e:
            return Verdict(False, str(e), checks)
        return Verdict(True, "OK", checks)


def verify_input(raw: bytes, index: int, outs: Sequence[sigopref.Output],
                 discourage_unknown_keys: bool = False) -> Verdict:
    return Spend(raw, outs).verify(index, discourage_unknown_keys)


# -- the leaf's bytes ---------------------------------------------------------------

def _decode(script: bytes):
    """(opcode, pushed data or None) of each operation of a leaf in turn; a
    push that runs past the end fails the input there (BIP 342: BAD_OPCODE)."""
    at = 0
    while at < len(script):
        op = script[at]
        at += 1
        if op > OP_PUSHDATA4:
            yield op, None
            continue
        size, width = op, 0
        if op >= OP_PUSHDATA1:
            width = 1 << (op - OP_PUSHDATA1)
            if at + width > len(script):
                raise _Fail("BAD_OPCODE")
            size = int.from_bytes(script[at : at + width], "little")
        at += width
        if at + size > len(script):
            raise _Fail("BAD_OPCODE")
        yield op, script[at : at + size]
        at += size


def _witness_size(witness: Sequence[bytes]) -> int:
    """Bytes of an input's witness as serialized: the count of items, then
    each item behind its length (what BIP 342's budget starts from)."""
    n = len(witness)
    return (1 if n < 0xFD else 3 if n <= 0xFFFF else 5) + sum(len(_varbytes(w)) for w in witness)


def _number(b: bytes) -> int:
    """A stack item as CScriptNum reads it: little-endian, the top bit of
    the last byte the sign, at most four bytes."""
    if len(b) > 4:
        raise Unsupported("a number longer than four bytes")
    if not b:
        return 0
    v = int.from_bytes(b, "little")
    if b[-1] & 0x80:
        return -(v & ~(0x80 << (8 * (len(b) - 1))))
    return v


def _encode_number(v: int) -> bytes:
    if not v:
        return b""
    mag = abs(v)
    out = bytearray(mag.to_bytes((mag.bit_length() + 7) // 8, "little"))
    if out[-1] & 0x80:
        out.append(0x80 if v < 0 else 0)
    elif v < 0:
        out[-1] |= 0x80
    return bytes(out)


def _truth(b: bytes) -> bool:
    """CastToBool: any non-zero byte, but for a negative zero."""
    return any(b[:-1]) or (bool(b) and b[-1] not in (0, 0x80))
