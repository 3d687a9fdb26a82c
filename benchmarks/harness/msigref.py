"""The plain reference of one P2WSH bare-multisig input, to its `ScriptError`.

`harness/sigopref.py` walks CHECKMULTISIG's cursor and says which pairings
it tried and whether the walk ended true. A corrupted twin needs the rest
of the verdict: which `ScriptError` Core's interpreter ends such an input
in under the consensus flags of a block (P2SH, DERSIG, CLTV, CSV, WITNESS,
NULLDUMMY, TAPROOT; nothing of policy: no NULLFAIL, no LOW_S, no
STRICTENC, no WITNESS_PUBKEYTYPE). Written out from `interpreter.cpp`
(`VerifyWitnessProgram`, `ExecuteWitnessScript`, `OP_CHECKMULTISIG`,
`IsValidSignatureEncoding`) over `sigopref`'s reader, walk and BIP 143
digest and `ecverify`'s curve code. It imports nothing of the program, and
what it does not implement raises `Unsupported`.
"""

from __future__ import annotations

import hashlib
from typing import List, NamedTuple, Sequence, Tuple, Union

from . import sigopref

MAX_SCRIPT_SIZE = 10_000
MAX_SCRIPT_ELEMENT_SIZE = 520
SIGHASH_ALL = 1


class Unsupported(Exception):
    """A rule this reference does not implement decides the input."""


class Verdict(NamedTuple):
    ok: bool
    error: str  # the ScriptError's name, "OK" for a passing input
    # (signature, key) in push order, as the walk tried them; empty where
    # the script ended before or inside the walk (a commitment, an encoding)
    tried: List[Tuple[int, int]]


class _Ends(Exception):
    """The script ends here with this ScriptError."""


def valid_der(sig: bytes) -> bool:
    """`IsValidSignatureEncoding`: strict DER of (r, s) and one hash-type
    byte after it, as BIP 66 writes it out."""
    if not 9 <= len(sig) <= 73 or sig[0] != 0x30 or sig[1] != len(sig) - 3:
        return False
    len_r = sig[3]
    if 5 + len_r >= len(sig):
        return False
    len_s = sig[5 + len_r]
    if len_r + len_s + 7 != len(sig):
        return False
    for at, size in ((2, len_r), (4 + len_r, len_s)):
        if sig[at] != 0x02 or size == 0 or sig[at + 2] & 0x80:
            return False
        if size > 1 and sig[at + 2] == 0 and not sig[at + 3] & 0x80:
            return False
    return True


def _pairing(inner: sigopref.Pairing) -> sigopref.Pairing:
    """One step of the walk as Core makes it: the signature's encoding
    first (DERSIG: an empty one passes and verifies against nothing), then
    the curve."""
    def pairing(sig: bytes, key: bytes) -> bool:
        if not sig:
            return False
        if not valid_der(sig):
            raise _Ends("SIG_DER")
        if sig[-1] != SIGHASH_ALL:
            raise Unsupported(f"hash type {sig[-1]:#x}: only SIGHASH_ALL's digest is written out")
        if len(key) != 33 or key[0] not in (2, 3):
            raise Unsupported("a key that is not compressed (lawful, and not in the traffic)")
        return inner(sig, key)

    return pairing


def verify_input(tx: Union[bytes, sigopref.Tx], index: int,
                 spent: Sequence[sigopref.Output]) -> Verdict:
    """Input `index` of `tx` (raw bytes or parsed), which spends
    `spent[index]`, a P2WSH output, with the witness `<dummy> <sigs>
    <script>` of a bare `m <keys> n CHECKMULTISIG`."""
    if isinstance(tx, (bytes, bytearray)):
        tx = sigopref.parse_tx(bytes(tx))
    amount, spk = spent[index]
    txin = tx.vin[index]
    if len(spk) != 34 or spk[:2] != b"\x00\x20" or txin.script_sig:
        raise Unsupported("not a native P2WSH spend")
    witness = txin.witness
    if not witness:
        return Verdict(False, "WITNESS_PROGRAM_WITNESS_EMPTY", [])
    script, stack = witness[-1], witness[:-1]
    if hashlib.sha256(script).digest() != spk[2:]:
        return Verdict(False, "WITNESS_PROGRAM_MISMATCH", [])
    if len(script) > MAX_SCRIPT_SIZE or any(len(x) > MAX_SCRIPT_ELEMENT_SIZE for x in stack):
        raise Unsupported("a script or an element over the size limits")
    try:
        m, keys = sigopref.parse_bare_multisig(script)
    except ValueError as e:
        raise Unsupported(f"the witness script: {e}") from e
    if len(stack) != m + 1:
        raise Unsupported("a stack that is not one dummy and m signatures")
    try:
        tried, ok = sigopref.multisig_walk(
            m, keys, stack[1:], _pairing(sigopref.ec_pairing(tx, index, script, amount)))
    except _Ends as e:
        return Verdict(False, str(e), [])
    if stack[0]:
        return Verdict(False, "SIG_NULLDUMMY", tried)
    # One element is left, the opcode's result: CLEANSTACK cannot trip.
    return Verdict(ok, "OK" if ok else "EVAL_FALSE", tried)
