"""Core's pre-BIP143 `SignatureHash` through `connect_block`: one legacy
transaction whose inputs cover what the digest can be made of.

`benchmarks/configs/worst-block-quadratic.json` runs one transaction of
5,569 P2PKH inputs on the chip (228 kB hashed an input, 1.27 GB a
connect). Here the same code runs small on the CPU, on the 16-lane rung:
**twelve inputs of one transaction**: the six hash types, SIGHASH_SINGLE
past the outputs (the digest that is the number one), an uncompressed and a
hybrid key, a bare `<key> CHECKSIG`, an OP_CODESEPARATOR before and inside
the script code, and a script that holds its own signature
(`FindAndDelete`). Each is signed over the plain reference's digest
(`benchmarks/harness/sighashref.py`: the serialiser written out with
`hashlib`) and compared three ways: the program (native interpreter, device
curve), the host oracle (the pure-Python interpreter, its own serialiser)
and the reference (its own curve code). **The corruptions** are the
benchmark driver's three. **The counter**:
`consensus_sighash_bytes_total{kind="legacy"}` rises by exactly the
reference's summed preimage lengths, and the `block.connect` span record
carries the same number. `consensus_sighash_template_total{event}`: the
transaction lays its blanked serialisation down once, and once more with
the sequences zeroed for SIGHASH_NONE and SIGHASH_SINGLE, and every digest
without SIGHASH_ANYONECANPAY is hashed from one of the two.
"""

import hashlib
import struct

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from benchmarks.generators import chain
from benchmarks.harness import ec, oracle, sighashref, signer, sigopref
from benchmarks.harness.sighashref import (
    SIGHASH_ALL,
    SIGHASH_ANYONECANPAY,
    SIGHASH_NONE,
    SIGHASH_SINGLE,
    push,
)
from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.flags import VERIFY_DERSIG, VERIFY_P2SH, height_to_flags
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.core.sighash import legacy_sighash
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import Coin, CoinsView, connect_block
from bitcoinconsensus_tpu.obs import add_sink, remove_sink
from bitcoinconsensus_tpu.utils.hashes import hash160

from test_native_block import to_native_view
from test_worst_block import SpecCurve, _total, same_result, to_python_copy

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: the 16-lane rung
]

HEIGHT = 364_292  # the megatransaction's block: P2SH and DERSIG, nothing later
AMOUNT = 1_000_000
N_OUTPUTS = 4
ACP = SIGHASH_ANYONECANPAY
OP_DROP, OP_CODESEPARATOR, OP_CHECKSIG = b"\x75", b"\xab", b"\xac"

BYTES, SECONDS = "consensus_sighash_bytes_total", "consensus_sighash_seconds_total"
TEMPLATES = "consensus_sighash_template_total"


def _sk(tag: str) -> int:
    return int.from_bytes(hashlib.sha256(tag.encode()).digest(), "big") % (ec.N - 1) + 1


def _key(sk: int, form: str) -> bytes:
    x, y = ec.g_mul(sk)
    if form == "compressed":
        return bytes([2 + (y & 1)]) + x.to_bytes(32, "big")
    lead = 4 if form == "uncompressed" else 6 + (y & 1)  # hybrid: 06 even, 07 odd
    return bytes([lead]) + x.to_bytes(32, "big") + y.to_bytes(32, "big")


def _p2pkh(key: bytes) -> bytes:
    return b"\x76\xa9\x14" + hash160(key) + b"\x88\xac"


# name -> (hash type, key form, shape of the output it spends)
CASES = {
    "all-uncompressed": (SIGHASH_ALL, "uncompressed", "p2pkh"),
    "single": (SIGHASH_SINGLE, "compressed", "p2pkh"),
    "single-anyonecanpay": (SIGHASH_SINGLE | ACP, "compressed", "p2pkh"),
    "none": (SIGHASH_NONE, "compressed", "p2pkh"),
    "none-anyonecanpay": (SIGHASH_NONE | ACP, "uncompressed", "p2pkh"),
    "all-anyonecanpay": (SIGHASH_ALL | ACP, "compressed", "p2pkh"),
    "single-past-the-outputs": (SIGHASH_SINGLE, "compressed", "p2pkh"),
    "bare-checksig": (SIGHASH_ALL, "compressed", "bare"),
    "codeseparator-inside": (SIGHASH_ALL, "compressed", "separator-after"),
    "codeseparator-before-and-inside": (SIGHASH_NONE | ACP, "uncompressed", "separator-both"),
    "find-and-delete": (SIGHASH_ALL, "compressed", "holds-its-signature"),
    "all-hybrid": (SIGHASH_ALL, "hybrid", "p2pkh"),
}
NAMES = list(CASES)
# the digests hashed from the transaction's blanked template: every one
# without SIGHASH_ANYONECANPAY but the one that is the number one
SERVED = [n for n, (hash_type, _, _) in CASES.items()
          if not hash_type & ACP and n != "single-past-the-outputs"]
assert NAMES.index("single-anyonecanpay") < N_OUTPUTS <= NAMES.index("single-past-the-outputs")


class Spend:
    """One input of the transaction: the coin's script, and the scriptSig
    once the transaction's other fields stand."""

    def __init__(self, name: str, position: int):
        self.name, self.position = name, position
        self.hash_type, form, self.shape = CASES[name]
        self.sk = _sk(f"quadratic/{name}")
        self.key = _key(self.sk, form)
        self.outpoint = OutPoint(hashlib.sha256(f"quadratic/op/{name}".encode()).digest(), position)
        checksig = push(self.key) + OP_CHECKSIG
        # the script code CHECKSIG hashes, before OP_CODESEPARATORs are left out
        self.script_code = {
            "p2pkh": _p2pkh(self.key),
            "bare": checksig,
            "separator-after": checksig + OP_CODESEPARATOR,
            "separator-both": checksig + OP_CODESEPARATOR,  # what follows the executed one
            "holds-its-signature": OP_DROP + checksig,  # with the signature's push cut out
        }[self.shape]
        self.spk = None  # known once signed, where the script holds the signature
        self.script_sig = b""

    def sign(self, tx: sigopref.Tx, digest: bytes = None) -> None:
        if digest is None:
            digest, _ = sighashref.signature_hash(tx, self.position, self.script_code, self.hash_type)
        self.sig = ec.sign_ecdsa(self.sk, digest) + bytes([self.hash_type])
        self.finish(self.sig)

    def finish(self, sig: bytes) -> None:
        self.script_sig = push(sig) + (push(self.key) if self.shape == "p2pkh" else b"")
        self.spk = {
            "separator-both": OP_CODESEPARATOR + self.script_code,
            "holds-its-signature": push(self.sig) + self.script_code,
        }.get(self.shape, self.script_code)


def _unsigned(spends) -> sigopref.Tx:
    vin = [sigopref.TxIn(sp.outpoint.hash, sp.outpoint.n, b"", 0xFFFFFFF0 + sp.position, [])
           for sp in spends]
    vout = [(AMOUNT + i, b"\x76\xa9\x14" + bytes([i]) * 20 + b"\x88\xac") for i in range(N_OUTPUTS)]
    return sigopref.Tx(1, vin, vout, 0)


def _leaky_preimage(tx: sigopref.Tx, spends) -> bytes:
    """SIGHASH_ALL's preimage as a signer would make it who forgot to blank
    the other inputs' scripts: every input carries the script it spends."""
    ins = [i.prev_hash + struct.pack("<I", i.prev_n) + sighashref.serialize_script_code(sp.spk)
           + struct.pack("<I", i.sequence) for i, sp in zip(tx.vin, spends)]
    outs = [struct.pack("<q", v) + bytes([len(s)]) + s for v, s in tx.vout]
    return b"".join([struct.pack("<i", tx.version), bytes([len(ins)]), *ins,
                     bytes([len(outs)]), *outs, struct.pack("<I", tx.locktime),
                     struct.pack("<i", SIGHASH_ALL)])


CORRUPTIONS = ("signature-bit", "scripts-not-blanked", "hash-type-changed")
VICTIM = NAMES.index("all-uncompressed")


def build(corruption=None):
    """(block, raw transaction, spent outputs, python coins) of the twelve
    inputs, sound or with the victim corrupted the driver's way."""
    spends = [Spend(name, i) for i, name in enumerate(NAMES)]
    tx = _unsigned(spends)
    for sp in spends:
        sp.sign(tx)
    victim = spends[VICTIM]
    if corruption == "signature-bit":
        victim.finish(victim.sig[:9] + bytes([victim.sig[9] ^ 1]) + victim.sig[10:])
    elif corruption == "scripts-not-blanked":
        leaky = _leaky_preimage(tx, spends)
        victim.sign(tx, hashlib.sha256(hashlib.sha256(leaky).digest()).digest())
    elif corruption == "hash-type-changed":
        victim.finish(victim.sig[:-1] + bytes([SIGHASH_NONE]))
    coins = CoinsView()
    for sp in spends:
        coins.add(sp.outpoint, Coin(TxOut(AMOUNT, sp.spk), height=1, coinbase=False))
    signed = Tx(
        version=1,
        vin=[TxIn(sp.outpoint, sp.script_sig, i.sequence) for sp, i in zip(spends, tx.vin)],
        vout=[TxOut(v, s) for v, s in tx.vout], locktime=0)
    fee = AMOUNT * len(spends) - sum(v for v, _ in tx.vout)
    block = chain.mine([signed], HEIGHT, fee, b"\x00" * 32, 1_436_500_000)
    return block, signed.serialize(), [(AMOUNT, sp.spk) for sp in spends], coins


class _Records:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)


def _work() -> dict:
    work = {(name, kind): _total(name, kind=kind)
            for name in (BYTES, SECONDS) for kind in ("legacy", "bip143")}
    work.update({(TEMPLATES, event): _total(TEMPLATES, event=event)
                 for event in ("built", "served")})
    return work


def connect(block, coins) -> dict:
    """The program's connect on a native view, with what it counted, the
    signature cache it filled and its `block.connect` span record."""
    view = to_native_view(coins)
    digest = view.digest()
    sig_cache, sink = SigCache(), _Records()
    before, lanes = _work(), _total("consensus_dispatch_lanes_total")
    add_sink(sink)
    try:
        res = connect_block(block.serialize(), view, HEIGHT, pow_limit=signer.REGTEST_POW_LIMIT,
                            verifier=TpuSecpVerifier(min_batch=16, chunk=16), sig_cache=sig_cache,
                            script_cache=ScriptExecutionCache())
    finally:
        remove_sink(sink)
    (span,) = [r for r in sink.records if r["name"] == "block.connect"]
    return {"res": res, "rose": {k: v - before[k] for k, v in _work().items()},
            "lanes": _total("consensus_dispatch_lanes_total") - lanes,
            "cached": len(sig_cache), "span": span["attrs"],
            "untouched": len(view) == len(coins._map) and view.digest() == digest}


def spec_connect(block, coins):
    """`connect_block` on the pure-Python interpreter and `secp_host`."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(native_bridge, "available", lambda: False)
        return connect_block(block, coins, HEIGHT, pow_limit=signer.REGTEST_POW_LIMIT,
                             verifier=SpecCurve(), sig_cache=SigCache(),
                             script_cache=ScriptExecutionCache())


def verdict(r) -> tuple:
    return bool(r.ok), "OK" if r.ok else ScriptError(int(r.script_error)).name


@pytest.fixture(scope="module")
def connected():
    """Per corruption (None: the sound block): the block connected once by
    the program and once by the executable spec, every input through the
    host oracle and through the plain reference."""
    made = {}
    flags = height_to_flags(HEIGHT, extended=True)
    assert flags == VERIFY_P2SH | VERIFY_DERSIG  # what sighashref.py states it implements

    def get(corruption=None) -> dict:
        if corruption not in made:
            block, raw, outs, coins = build(corruption)
            made[corruption] = {
                "block": block, "raw": raw, "outs": outs,
                "refs": [sighashref.verify_input(raw, i, outs) for i in range(len(outs))],
                "oracle": [oracle.oracle_verdict(raw, i, outs, flags) for i in range(len(outs))],
                "spec": spec_connect(block, to_python_copy(coins)),
                "got": connect(block, coins),
            }
        return made[corruption]

    return get


# -- the sound block ---------------------------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_an_input_passes_three_ways(connected, name):
    b, i = connected(), NAMES.index(name)
    ref = b["refs"][i]
    assert verdict(b["got"]["res"].input_results[i]) == (True, "OK")
    assert b["oracle"][i] == oracle.as_triple(b["got"]["res"].input_results[i])
    assert b["oracle"][i][0] is True
    assert (ref.ok, ref.error) == (True, "OK")
    # what the reference hashed for it: nothing where the digest is the number one
    assert (ref.preimage_bytes == 0) == (name == "single-past-the-outputs")


def test_the_block_connects_and_counts_the_bytes_the_reference_hashed(connected):
    b = connected()
    got, res = b["got"], b["got"]["res"]
    same_result(res, b["spec"])
    assert res.ok and got["cached"] == got["lanes"] == len(NAMES) and not got["untouched"]
    cost = sigopref.block_sigop_cost(
        sigopref.parse_tx(b["block"].vtx[0].serialize()), [(sigopref.parse_tx(b["raw"]), b["outs"])])
    assert res.sigop_cost == cost == 4 * N_OUTPUTS  # the four P2PKH outputs; no scriptSig has one
    hashed = sum(v.preimage_bytes for v in b["refs"])
    assert got["rose"][BYTES, "legacy"] == hashed > 0
    assert got["rose"][BYTES, "bip143"] == 0 == got["rose"][SECONDS, "bip143"]
    assert got["rose"][SECONDS, "legacy"] > 0
    assert got["span"]["sighash_bytes"] == hashed
    # one template with the sequences as they are, one with them zeroed
    assert got["rose"][TEMPLATES, "built"] == 2 == got["span"]["sighash_template_built"]
    assert got["rose"][TEMPLATES, "served"] == len(SERVED) == 7
    assert got["span"]["sighash_template_served"] == len(SERVED)


# -- the driver's three corruptions ---------------------------------------------------

@pytest.mark.parametrize("corruption", CORRUPTIONS)
def test_a_corrupted_block_is_rejected_for_its_victim_three_ways(connected, corruption):
    b = connected(corruption)
    got, res = b["got"], b["got"]["res"]
    same_result(res, b["spec"])
    assert not res.ok and res.reason == "block-validation-failed"
    assert res.script_failures == [VICTIM] and got["untouched"]
    for i in range(len(NAMES)):
        want = (False, "EVAL_FALSE") if i == VICTIM else (True, "OK")
        assert verdict(res.input_results[i]) == want
        assert b["oracle"][i] == oracle.as_triple(res.input_results[i])
        assert (b["refs"][i].ok, b["refs"][i].error) == want
    assert got["cached"] == len(NAMES) - 1  # success-only
    # round one guessed the victim's check true; round two hashed its digest again
    hashed = sum(v.preimage_bytes for v in b["refs"])
    assert got["rose"][BYTES, "legacy"] == hashed + b["refs"][VICTIM].preimage_bytes
    assert got["span"]["sighash_bytes"] == got["rose"][BYTES, "legacy"]
    # and from the template round one laid down: use never changes it
    assert got["rose"][TEMPLATES, "built"] == 2
    assert got["rose"][TEMPLATES, "served"] == len(SERVED) + 1
    assert got["span"]["sighash_template_served"] == len(SERVED) + 1


# -- the reference's digest against the program's two serialisers ------------------------

HASH_TYPES = [SIGHASH_ALL, SIGHASH_NONE, SIGHASH_SINGLE, SIGHASH_ALL | ACP, SIGHASH_NONE | ACP,
              SIGHASH_SINGLE | ACP, 0, 0x04, 0x1F, 0xFF]


@pytest.mark.parametrize("hash_type", HASH_TYPES, ids=[f"{h:#04x}" for h in HASH_TYPES])
def test_the_references_digest_is_the_programs(hash_type):
    """Defined or not, a hash-type byte is hashed as Core hashes it: the
    reference, written from the description, against `core/sighash.py`."""
    _block, raw, outs, _coins = build()
    tx, ptx = sigopref.parse_tx(raw), Tx.deserialize(raw)
    code = b"\x76" + OP_CODESEPARATOR + outs[0][1]
    for index in (0, N_OUTPUTS - 1, N_OUTPUTS, len(outs) - 1):
        digest, size = sighashref.signature_hash(tx, index, code, hash_type)
        assert digest == legacy_sighash(code, ptx, index, hash_type)
        one = hash_type & 0x1F == SIGHASH_SINGLE and index >= N_OUTPUTS
        assert (digest == sighashref.ONE and size == 0) if one else size > 80
