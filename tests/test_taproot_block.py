"""A block of the taproot era: key-path spends, script-path spends through
BIP 342's k-of-n `OP_CHECKSIGADD` script and through a lone `OP_CHECKSIG`
leaf, and the commitment check, on the index path.

`benchmarks/configs/taproot-block.json` runs 10,800 such inputs on the chip
(14,040 curve checks, 88.5 % of them Schnorr or tweak lanes). Here every
shape and every way it can fail runs small on the CPU, one `verify_batch`
a case on the warmed 8-lane rung, and is compared three ways on verdict and
`ScriptError`: the index path (native interpreter, deferred lanes, the
device kernel), the executable spec (`harness/oracle.py`: the pure-Python
interpreter over `secp_host`) and the plain BIP 341/342 reference
(`harness/tapref.py`, which shares nothing with either). Then one
`connect_block` of a 12-input block of the four kinds on the native view.
"""

import hashlib
import random

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from benchmarks.harness import ec, oracle, signer, sigopref, tapref, tapsigner
from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.flags import (
    VERIFY_DISCOURAGE_UPGRADABLE_PUBKEYTYPE,
    height_to_flags,
)
from bitcoinconsensus_tpu.core.script import push_data
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.core.sighash import (
    SIGHASH_ALL,
    PrecomputedTxData,
    SigVersion,
    bip143_sighash,
    bip341_sighash,
)
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import connect_block
from bitcoinconsensus_tpu.obs import add_sink, get_registry, remove_sink

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: the 8- and 16-lane rungs
]

HEIGHT = 710_000
FLAGS = height_to_flags(HEIGHT, extended=True)
AMOUNT = 1_000_000
OP_CHECKSIG, OP_CHECKSIGADD, OP_CHECKMULTISIG, OP_NUMEQUAL = 0xAC, 0xBA, 0xAE, 0x9C


def _sk(seed: str) -> int:
    return signer._sk(f"test_taproot_block/{seed}")


def _flip(b: bytes, i: int, bit: int = 1) -> bytes:
    return b[:i] + bytes([b[i] ^ bit]) + b[i + 1 :]


class Leaf:
    """A script-path output with `n` leaf keys, and the one-input
    transaction that spends it: everything a case needs to fill a witness
    by hand."""

    def __init__(self, name: str, script_of, n: int = 3, depth: int = 2):
        self.sks = [_sk(f"{name}/k{i}") for i in range(n)]
        self.keys = [ec.xonly_pubkey_create(sk)[0] for sk in self.sks]
        siblings = [hashlib.sha256(f"{name}/s{j}".encode()).digest() for j in range(depth)]
        self.leaf = tapsigner.TapLeaf(_sk(f"{name}/internal"), script_of(self.keys), siblings)
        self.tx, self.outs, self.txdata = one_input_tx(self.leaf.spk, name)

    def sigs(self, signers, hash_type: int = 0):
        """A signature (64 bytes, or 65 with a hash type) for each key in
        `signers`, an empty vector for every other, in script order."""
        digest = self.leaf.sighash(self.tx, 0, self.txdata, hash_type)
        tail = bytes([hash_type]) if hash_type else b""
        return [ec.sign_schnorr(sk, digest) + tail if i in signers else b""
                for i, sk in enumerate(self.sks)]

    def spend(self, sigs, control=None, script=None) -> tuple:
        """(raw, outs) with the witness `sigs` (script order; the first
        key's goes on top), the script, the control block."""
        control = self.leaf.control() if control is None else control
        script = self.leaf.script if script is None else script
        self.tx.vin[0].witness = list(sigs)[::-1] + [script, control]
        self.tx.invalidate_caches()
        return self.tx.serialize(), self.outs


def one_input_tx(spk: bytes, name: str):
    op = OutPoint(hashlib.sha256(f"test_taproot_block/{name}/op".encode()).digest(), 1)
    tx = Tx(version=2, vin=[TxIn(op)], vout=[TxOut(AMOUNT - 1000, b"\x51\x20" + b"\x07" * 32),
                                             TxOut(0, b"\x6a")], locktime=0)
    outs = [(AMOUNT, spk)]
    return tx, outs, PrecomputedTxData(tx, [TxOut(AMOUNT, spk)], force=True)


def csa(k: int):
    return lambda keys: tapsigner.csa_script(keys, k)


def lone(keys):
    return tapsigner.leaf_script(keys[0])


# -- the cases: name -> () -> (raw, outs, expected ScriptError, extra flags) -------

CASES = {}


def case(name: str, error: str = "OK", flags: int = 0):
    def register(build):
        CASES[name] = (build, error, flags)
        return build
    return register


@case("key-path")
def _():
    w = signer.Wallet("test_taproot_block/key-path", "p2tr")
    tx, outs, txdata = one_input_tx(w.spk, "key-path")
    w.sign_input(tx, 0, AMOUNT, txdata=txdata)
    return tx.serialize(), outs


@case("key-path-sighash-all-65-bytes")
def _():
    w = signer.Wallet("test_taproot_block/key-path-all", "p2tr")
    tx, outs, txdata = one_input_tx(w.spk, "key-path-all")
    digest = bip341_sighash(tx, 0, SIGHASH_ALL, SigVersion.TAPROOT, txdata, False, b"")
    tx.vin[0].witness = [ec.sign_schnorr(w.out_sk, digest) + bytes([SIGHASH_ALL])]
    return tx.serialize(), outs


@case("key-path-flipped-signature", "SCHNORR_SIG")
def _():
    w = signer.Wallet("test_taproot_block/key-path-bad", "p2tr")
    tx, outs, txdata = one_input_tx(w.spk, "key-path-bad")
    w.sign_input(tx, 0, AMOUNT, txdata=txdata, corrupt=True)
    return tx.serialize(), outs


@case("key-path-65-bytes-hash-type-0", "SCHNORR_SIG_HASHTYPE")
def _():
    w = signer.Wallet("test_taproot_block/key-path-ht0", "p2tr")
    tx, outs, txdata = one_input_tx(w.spk, "key-path-ht0")
    w.sign_input(tx, 0, AMOUNT, txdata=txdata)
    tx.vin[0].witness = [tx.vin[0].witness[0] + b"\x00"]
    return tx.serialize(), outs


for _depth in (0, 1, 2):
    @case(f"lone-leaf-depth-{_depth}")
    def _(depth=_depth):
        c = Leaf(f"lone-{depth}", lone, n=1, depth=depth)
        return c.spend(c.sigs({0}))

for _empty in (0, 1, 2):
    @case(f"csa-2of3-empty-vector-at-{_empty}")
    def _(empty=_empty):
        c = Leaf(f"csa-empty-{empty}", csa(2))
        return c.spend(c.sigs({0, 1, 2} - {empty}))


@case("csa-3of3")
def _():
    c = Leaf("csa-3of3", csa(3))
    return c.spend(c.sigs({0, 1, 2}))


@case("csa-2of3-single-anyonecanpay-65-bytes")
def _():
    c = Leaf("csa-sighash-83", csa(2))
    return c.spend(c.sigs({0, 2}, hash_type=0x83))


for _at in (0, 1, 2):
    @case(f"csa-3of3-flipped-signature-{_at}", "SCHNORR_SIG")
    def _(at=_at):
        c = Leaf(f"csa-flip-{at}", csa(3))
        sigs = c.sigs({0, 1, 2})
        sigs[at] = _flip(sigs[at], 40)
        return c.spend(sigs)


@case("control-block-sibling-flipped", "WITNESS_PROGRAM_MISMATCH")
def _():
    c = Leaf("sibling", csa(2))
    return c.spend(c.sigs({0, 1}), control=_flip(c.leaf.control(), 33 + 7))


@case("control-block-internal-key-flipped", "WITNESS_PROGRAM_MISMATCH")
def _():
    c = Leaf("internal", lone, n=1)
    return c.spend(c.sigs({0}), control=_flip(c.leaf.control(), 1 + 20))


@case("control-block-wrong-parity", "WITNESS_PROGRAM_MISMATCH")
def _():
    c = Leaf("parity", lone, n=1)
    return c.spend(c.sigs({0}), control=_flip(c.leaf.control(), 0))


@case("control-block-33+32m+1-bytes", "TAPROOT_WRONG_CONTROL_SIZE")
def _():
    c = Leaf("control-size", lone, n=1)
    return c.spend(c.sigs({0}), control=c.leaf.control() + b"\x00")


@case("csa-2of3-one-signature-too-few", "EVAL_FALSE")
def _():
    c = Leaf("too-few", csa(2))
    return c.spend(c.sigs({1}))


@case("csa-2of3-invalid-signature-where-empty-would-pass", "SCHNORR_SIG")
def _():
    c = Leaf("not-empty", csa(2))
    sigs = c.sigs({0, 1})
    sigs[2] = _flip(c.sigs({2})[2], 5)
    return c.spend(sigs)


@case("leaf-signature-65-bytes-hash-type-0", "SCHNORR_SIG_HASHTYPE")
def _():
    c = Leaf("leaf-ht0", lone, n=1)
    return c.spend([c.sigs({0})[0] + b"\x00"])


def unknown_keys(n: int):
    """`n` keys of an unknown type (33 bytes) under CHECKSIG / CHECKSIGADD
    and `n NUMEQUAL`: each one-byte "signature" passes unchecked and costs
    50 units of the budget, of which the witness brings 37 a key."""
    def script(_keys):
        out = b""
        for i in range(n):
            out += push_data(bytes([2]) + bytes([i + 1]) * 32)
            out += bytes([OP_CHECKSIGADD if i else OP_CHECKSIG])
        return out + bytes([0x50 + n, OP_NUMEQUAL])
    return script


@case("validation-weight-covers-six-sigops")
def _():
    c = Leaf("weight-6", unknown_keys(6), n=0, depth=0)
    return c.spend([b"\x01"] * 6)


@case("validation-weight-one-sigop-short", "TAPSCRIPT_VALIDATION_WEIGHT")
def _():
    c = Leaf("weight-7", unknown_keys(7), n=0, depth=0)
    return c.spend([b"\x01"] * 7)


@case("unknown-key-type-passes-unchecked")
def _():
    c = Leaf("unknown-key", unknown_keys(1), n=0)
    return c.spend([b"\x01"])


@case("unknown-key-type-discouraged", "DISCOURAGE_UPGRADABLE_PUBKEYTYPE",
      VERIFY_DISCOURAGE_UPGRADABLE_PUBKEYTYPE)
def _():
    c = Leaf("unknown-key-policy", unknown_keys(1), n=0)
    return c.spend([b"\x01"])


@case("checkmultisig-in-a-leaf", "TAPSCRIPT_CHECKMULTISIG")
def _():
    c = Leaf("checkmultisig", lambda keys: b"\x00\x00" + bytes([OP_CHECKMULTISIG]), n=0)
    return c.spend([])


@case("checksigadd-under-witness-v0", "BAD_OPCODE")
def _():
    sk = _sk("v0/k")
    pub = ec.pubkey_create(sk)
    script = b"\x00" + push_data(pub) + bytes([OP_CHECKSIGADD])
    spk = b"\x00\x20" + hashlib.sha256(script).digest()
    tx, outs, _ = one_input_tx(spk, "v0")
    sig = ec.sign_ecdsa(sk, bip143_sighash(script, tx, 0, SIGHASH_ALL, AMOUNT)) + bytes([SIGHASH_ALL])
    tx.vin[0].witness = [sig, script]
    return tx.serialize(), outs


@case("p2wpkh")
def _():
    w = signer.Wallet("test_taproot_block/p2wpkh", "p2wpkh")
    tx, outs, _ = one_input_tx(w.spk, "p2wpkh")
    w.sign_input(tx, 0, AMOUNT)
    return tx.serialize(), outs


@case("p2wpkh-flipped-signature", "EVAL_FALSE")
def _():
    w = signer.Wallet("test_taproot_block/p2wpkh-bad", "p2wpkh")
    tx, outs, _ = one_input_tx(w.spk, "p2wpkh-bad")
    w.sign_input(tx, 0, AMOUNT, corrupt=True)
    return tx.serialize(), outs


def _verdict(ok: bool, script_error) -> tuple:
    return bool(ok), "OK" if ok else ScriptError(int(script_error)).name


@pytest.mark.parametrize("name", list(CASES))
def test_index_path_equals_the_spec_and_the_plain_reference(name):
    build, error, extra = CASES[name]
    raw, outs = build()
    flags = FLAGS | extra
    want = (error == "OK", error)

    before = _lanes()
    res, = verify_batch([BatchItem(raw, 0, flags, spent_outputs=outs)], TpuSecpVerifier(),
                        SigCache(), ScriptExecutionCache())
    sent = sum(_lanes().values()) - sum(before.values())
    assert sent <= 7  # the 8-lane rung: one lane of it is the sentinel
    assert _verdict(res.ok, res.script_error) == want

    spec = oracle.oracle_verdict(raw, 0, outs, flags)
    assert _verdict(spec[0], spec[2]) == want

    if name == "checksigadd-under-witness-v0":
        with pytest.raises(tapref.Unsupported):  # a P2WSH spend is no rule of BIP 341/342
            tapref.verify_input(raw, 0, outs)
        return
    ref = tapref.verify_input(
        raw, 0, outs, discourage_unknown_keys=bool(extra & VERIFY_DISCOURAGE_UPGRADABLE_PUBKEYTYPE))
    assert (ref.ok, ref.error) == want
    if res.ok:  # a passing input sends what the reference checks, kind for kind
        assert {k: _lanes()[k] - before[k] for k in tapref.KINDS} == ref.checks


# -- one connect of the four kinds --------------------------------------------------

def _samples(name: str, label: str) -> dict:
    snap = get_registry().snapshot().get(name, {"samples": []})
    return {s["labels"][label]: s["value"] for s in snap["samples"]}


def _lanes() -> dict:
    got = _samples("consensus_checks_total", "kind")
    return {k: got.get(k, 0.0) for k in tapref.KINDS}


def _taproot_hashes() -> dict:
    got = _samples("consensus_taproot_hash_total", "what")
    return {k: got.get(k, 0.0) for k in ("sighash", "leaf", "branch", "tweak")}


KINDS = ["p2tr_key"] * 7 + ["p2tr_csa_2of3"] + ["p2tr_leaf_1"] + ["p2wpkh"] * 3
SIZES = [1, 2, 3, 6]  # 12 inputs, 15 curve checks: one dispatch of the 16-lane rung


class _Records:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)


def _block(corrupt=None):
    """(block, per-tx (raw, outs), coins): the 12 inputs in four
    transactions; `corrupt` = (input, corruption) for the twin."""
    from benchmarks.generators import tapblock

    rng = random.Random("test_taproot_block/connect")  # which key of the 2-of-3 stays silent
    wallets = [tapblock._wallet(f"test_taproot_block/connect/{i}", k, 2, rng)
               for i, k in enumerate(KINDS)]
    order = [0, 7, 9, 1, 8, 2, 10, 3, 4, 5, 11, 6]  # the kinds interleaved
    wallets = [wallets[i] for i in order]
    ops = [OutPoint(hashlib.sha256(b"test_taproot_block/connect/op%d" % i).digest(), i)
           for i in range(len(wallets))]
    txs, records, at = [], [], 0
    for size in SIZES:
        cut = slice(at, at + size)
        how = (corrupt[0] - at, corrupt[1]) if corrupt and at <= corrupt[0] < at + size else None
        tx = tapblock._spend(wallets[cut], [AMOUNT] * size, ops[cut],
                             b"\x51\x20" + bytes([size]) * 32, 1000, how)
        txs.append(tx)
        records.append((tx.serialize(), [(AMOUNT, w.spk) for w in wallets[cut]]))
        at += size
    block = signer.build_block(txs, HEIGHT, fees=1000 * len(txs))
    coins = [(op.hash, op.n, AMOUNT, 1, False, w.spk) for op, w in zip(ops, wallets)]
    return block, records, coins, [w.kind for w in wallets]


def _connect(block, coins):
    view = native_bridge.NativeCoinsView()
    view.add_coins_batch(coins)
    res = connect_block(block.serialize(), view, HEIGHT, pow_limit=signer.REGTEST_POW_LIMIT,
                        verifier=TpuSecpVerifier(min_batch=16, chunk=16),
                        sig_cache=SigCache(), script_cache=ScriptExecutionCache())
    return res, view


def _reference(records):
    """Every input through the plain reference: verdicts in block order,
    the curve checks it made by kind, the hashes a commitment check and a
    signature ask for."""
    verdicts, checks = [], dict.fromkeys(tapref.KINDS, 0)
    hashes = dict.fromkeys(("sighash", "leaf", "branch", "tweak"), 0)
    for raw, outs in records:
        spend = tapref.Spend(raw, outs)
        for i, txin in enumerate(spend.tx.vin):
            v = spend.verify(i)
            verdicts.append((v.ok, v.error))
            for k, n in v.checks.items():
                checks[k] += n
            hashes["sighash"] += v.checks["schnorr"]
            if v.checks["tweak"]:  # a script path: one leaf, a branch a sibling, one tweak
                hashes["leaf"] += 1
                hashes["branch"] += (len(txin.witness[-1]) - 33) // 32
                hashes["tweak"] += 1
    return verdicts, checks, hashes


def test_connect_block_of_the_four_kinds_equals_the_reference():
    block, records, coins, kinds = _block()
    verdicts, checks, hashes = _reference(records)
    assert all(ok for ok, _ in verdicts) and sum(checks.values()) == 15
    assert checks == {"ecdsa": 3, "schnorr": 7 + 2 + 1, "tweak": 2}
    cost = sigopref.block_sigop_cost(
        sigopref.parse_tx(block.vtx[0].serialize()),
        [(sigopref.parse_tx(raw), outs) for raw, outs in records])
    assert cost == 3  # a P2WPKH input is one; witness v1 adds none

    lanes0, hashes0, sink = _lanes(), _taproot_hashes(), _Records()
    add_sink(sink)
    try:
        res, view = _connect(block, coins)
    finally:
        remove_sink(sink)
    assert res.ok and res.sigop_cost == cost
    assert [_verdict(r.ok, r.script_error) for r in res.input_results] == verdicts
    assert {k: v - lanes0[k] for k, v in _lanes().items()} == checks
    assert {k: v - hashes0[k] for k, v in _taproot_hashes().items()} == hashes
    span, = [r for r in sink.records if r["name"] == "block.connect"]
    assert {k: span["attrs"][f"lanes_{k}"] for k in tapref.KINDS} == checks

    # The commitment-flipped twin: the victim's tweak lane fails on the
    # device, the block is rejected for it alone and the view stays.
    victim = next(i for i, k in enumerate(kinds) if k == "p2tr_csa_2of3")
    bad, bad_records, _, _ = _block(corrupt=(victim, "commitment"))
    bad_verdicts, _, _ = _reference(bad_records)
    assert [i for i, (ok, _) in enumerate(bad_verdicts) if not ok] == [victim]
    assert bad_verdicts[victim] == (False, "WITNESS_PROGRAM_MISMATCH")
    res, view = _connect(bad, coins)
    assert not res.ok and res.reason == "block-validation-failed"
    assert res.script_failures == [victim] and len(view) == len(coins)
    assert [_verdict(r.ok, r.script_error) for r in res.input_results] == bad_verdicts
