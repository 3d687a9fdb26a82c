"""`connect_block_stream` against a plain loop, and the view's undo record.

The reference of the semantics is a loop that shares nothing with the
code under test: for each block in order, `connect_block` through
`_connect_block_impl` on a Python `CoinsView` with a host verifier (no
native core, no device, no stream), stopping at the first failure. The
stream runs on a `NativeCoinsView` with the device verifier, at depth 1, 2
and 3, and has to give the same results and leave the same view, coin for
coin: on a valid chain, on a block that fails where it is begun, on one
that fails where it is finished, and when its consumer closes it early.

Chains are seeded and small: 5 blocks of 3 or 4 transactions, legacy P2PKH
and P2SH 2-of-3 inputs, in every block after the first an input that
spends an output the block before created, at heights that cross 419,328
so that CHECKSEQUENCEVERIFY comes in with blocks in flight. A block's
curve checks stay at 15 or fewer, on the rungs `warm_kernel` has warmed.
"""

import hashlib
import os
from contextlib import contextmanager

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.block import Block
from bitcoinconsensus_tpu.core.flags import HEIGHT_CSV, VERIFY_CHECKSEQUENCEVERIFY, height_to_flags
from bitcoinconsensus_tpu.core.script import OP_CHECKMULTISIG, push_data
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.core.sighash import SIGHASH_ALL, legacy_sighash
from bitcoinconsensus_tpu.core.tx import COIN, OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
from bitcoinconsensus_tpu.models import validate
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import (
    Coin,
    CoinsView,
    connect_block,
    connect_block_stream,
)
from bitcoinconsensus_tpu.obs import get_registry
from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject
from bitcoinconsensus_tpu.utils.blockgen import (
    REGTEST_POW_LIMIT,
    FundedOutput,
    Wallet,
    _flip,
    _sk,
    build_block,
)
from bitcoinconsensus_tpu.utils.hashes import hash160

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: first calls
    pytest.mark.limit(240),
]

DEPTHS = (1, 2, 3)
START = HEIGHT_CSV - 2  # blocks 0, 1 before CHECKSEQUENCEVERIFY, 2.. after
N_BLOCKS = 5
OP_CSV, OP_DROP, OP_CHECKSIG = 0xB2, 0x75, 0xAC


# ---------------------------------------------------------------------------
# Wallets the program's blockgen lacks: the pre-segwit kinds.


class P2shMultisig:
    """P2SH 2-of-3 bare multisig, compressed keys, legacy sighash."""

    kind = "p2sh_multisig"

    def __init__(self, seed: str):
        self.sks = [_sk(f"{seed}/k{i}") for i in range(3)]
        self.redeem = (
            b"\x52" + b"".join(push_data(H.pubkey_create(sk)) for sk in self.sks)
            + b"\x53" + bytes([OP_CHECKMULTISIG])
        )
        self.spk = b"\xa9\x14" + hash160(self.redeem) + b"\x87"

    def sign_input(self, tx, n_in, amount, corrupt=False):
        sighash = legacy_sighash(self.redeem, tx, n_in, SIGHASH_ALL)
        sigs = [H.sign_ecdsa(sk, sighash) + bytes([SIGHASH_ALL]) for sk in self.sks[:2]]
        if corrupt:
            sigs[0] = _flip(sigs[0], 9)
        tx.vin[n_in].script_sig = (
            b"\x00" + b"".join(push_data(s) for s in sigs) + push_data(self.redeem)
        )
        tx.invalidate_caches()


class P2shCsv:
    """P2SH `1 CHECKSEQUENCEVERIFY DROP <key> CHECKSIG`: a NOP before
    height 419,328, and from there a demand on the spending transaction's
    version (2 or more) that a version-1 spend fails."""

    kind = "p2sh_csv"

    def __init__(self, seed: str):
        self.sk = _sk(seed)
        self.redeem = (
            b"\x51" + bytes([OP_CSV, OP_DROP]) + push_data(H.pubkey_create(self.sk))
            + bytes([OP_CHECKSIG])
        )
        self.spk = b"\xa9\x14" + hash160(self.redeem) + b"\x87"

    def sign_input(self, tx, n_in, amount, corrupt=False):
        sighash = legacy_sighash(self.redeem, tx, n_in, SIGHASH_ALL)
        sig = H.sign_ecdsa(self.sk, sighash) + bytes([SIGHASH_ALL])
        tx.vin[n_in].script_sig = push_data(sig) + push_data(self.redeem)
        tx.invalidate_caches()


def _wallet(seed: str, kind: str):
    if kind == "p2sh_multisig":
        return P2shMultisig(seed)
    if kind == "p2sh_csv":
        return P2shCsv(seed)
    return Wallet(seed, kind)


def _spend(inputs, pay_to, fee=1000, version=2, corrupt_input=None):
    """One signed legacy tx: `inputs` (FundedOutputs) to one output a
    wallet of `pay_to`, which come back as FundedOutputs to spend next."""
    total = sum(f.amount for f in inputs) - fee
    share = total // len(pay_to)
    tx = Tx(
        version=version,
        vin=[TxIn(f.outpoint, sequence=1) for f in inputs],
        vout=[TxOut(share, w.spk) for w in pay_to],
        locktime=0,
    )
    for i, f in enumerate(inputs):
        f.wallet.sign_input(tx, i, f.amount, corrupt=(i == corrupt_input))
    made = [FundedOutput(OutPoint(tx.txid, n), w, share) for n, w in enumerate(pay_to)]
    return tx, made, total - share * len(pay_to) + fee


# ---------------------------------------------------------------------------
# A chain and the coins it starts from.


class Chain:
    """`blocks` (raw bytes) from `START`, the funded coins as tuples for
    either kind of view, and every outpoint the chain names."""

    def __init__(self, seed: str, *, bad_sig_block=None, missing_input_block=None,
                 csv_v1_block=1):
        self.coins = []
        self.outpoints = []
        self.blocks = []
        self.n_inputs = []
        n_funded = 0

        def fund(kind):
            nonlocal n_funded
            i = n_funded
            n_funded += 1
            w = _wallet(f"{seed}/{i}", kind)
            op = OutPoint(hashlib.sha256(f"{seed}/op/{i}".encode()).digest(), i)
            amount = COIN // 100 + i
            self.coins.append((op.hash, op.n, amount, 1, False, w.spk))
            self.outpoints.append(op)
            return FundedOutput(op, w, amount)

        carried = []  # P2PKH outputs the block before created
        for k in range(N_BLOCKS):
            txs, fees, made_here = [], 0, []
            # tx 0: two funded P2PKH and, after the first block, one output
            # of the block before; pays two fresh P2PKH wallets.
            ins = [fund("p2pkh"), fund("p2pkh")] + carried[:1]
            pay = [Wallet(f"{seed}/b{k}/o{j}", "p2pkh") for j in range(2)]
            tx, made, fee = _spend(
                ins, pay, corrupt_input=0 if k == bad_sig_block else None
            )
            txs.append(tx)
            fees += fee
            made_here += made
            # tx 1: one P2SH 2-of-3 and one P2PKH.
            tx, made, fee = _spend(
                [fund("p2sh_multisig"), fund("p2pkh")],
                [Wallet(f"{seed}/b{k}/m", "p2pkh")],
            )
            txs.append(tx)
            fees += fee
            # tx 2: spends an output tx 0 of this same block created.
            tx, made, fee = _spend(
                made_here[1:2], [Wallet(f"{seed}/b{k}/c", "p2pkh")]
            )
            txs.append(tx)
            fees += fee
            # tx 3, in two blocks: a CHECKSEQUENCEVERIFY script. Version 2
            # where stated, version 1 in block `csv_v1_block`.
            if k in (csv_v1_block, 3):
                tx, made, fee = _spend(
                    [fund("p2sh_csv")], [Wallet(f"{seed}/b{k}/s", "p2pkh")],
                    version=1 if k == csv_v1_block else 2,
                )
                txs.append(tx)
                fees += fee
            if k == missing_input_block:
                ghost = FundedOutput(
                    OutPoint(hashlib.sha256(b"ghost").digest(), 0),
                    Wallet(f"{seed}/ghost", "p2pkh"), COIN // 100,
                )
                tx, made, fee = _spend([ghost], [Wallet(f"{seed}/g", "p2pkh")])
                txs.append(tx)
                fees += fee
            carried = made_here[:1]
            for tx in txs:
                self.outpoints += [OutPoint(tx.txid, n) for n in range(len(tx.vout))]
            block = build_block(txs, START + k, fees=fees, witness_commitment=False)
            self.outpoints.append(OutPoint(block.vtx[0].txid, 0))
            self.blocks.append(block.serialize())
            self.n_inputs.append(sum(len(tx.vin) for tx in txs))

    def python_view(self) -> CoinsView:
        view = CoinsView()
        for txid, n, amount, height, cb, spk in self.coins:
            view.add(OutPoint(txid, n), Coin(TxOut(amount, spk), height, cb))
        return view

    def native_view(self) -> native_bridge.NativeCoinsView:
        view = native_bridge.NativeCoinsView()
        view.add_coins_batch(self.coins)
        return view


class HostVerifier:
    """The reference's verifier: every check on the pure-Python curve
    code, one at a time."""

    def verify_checks(self, checks):
        return [self._host_check(c) for c in checks]

    @staticmethod
    def _host_check(chk):
        assert chk.kind == "ecdsa"
        return H.verify_ecdsa(*chk.data)


@contextmanager
def no_native_core():
    """`native_bridge.lib()` reads the switch on every call."""
    before = os.environ.get("BITCOINCONSENSUS_TPU_NATIVE")
    os.environ["BITCOINCONSENSUS_TPU_NATIVE"] = "0"
    try:
        assert not native_bridge.available()
        yield
    finally:
        if before is None:
            del os.environ["BITCOINCONSENSUS_TPU_NATIVE"]
        else:
            os.environ["BITCOINCONSENSUS_TPU_NATIVE"] = before


def reference_loop(chain: Chain, start=START, blocks=None):
    """(results, view): the plain loop, stopping at the first failure."""
    view, out = chain.python_view(), []
    sig_cache, script_cache = SigCache(), ScriptExecutionCache()
    with no_native_core():
        for k, raw in enumerate(chain.blocks if blocks is None else blocks):
            res = connect_block(
                Block.deserialize(raw), view, start + k, verifier=HostVerifier(),
                pow_limit=REGTEST_POW_LIMIT, sig_cache=sig_cache,
                script_cache=script_cache,
            )
            out.append(res)
            if not res.ok:
                break
    return out, view


def as_tuple(res):
    inputs = None
    if res.input_results is not None:
        inputs = [(r.ok, r.error, r.script_error) for r in res.input_results]
    return (res.ok, res.reason, res.fees, res.sigop_cost, inputs)


def assert_same_coins(chain: Chain, nview, pview) -> None:
    """Coin for coin: the same number, and every outpoint the chain names
    either absent from both or equal in amount, script, height and flag."""
    assert len(nview) == len(pview)
    for op in chain.outpoints:
        a, b = nview.get(op), pview.get(op)
        assert (a is None) == (b is None), op
        if a is not None:
            assert (a.out.value, a.out.script_pubkey, a.height, a.coinbase) == (
                b.out.value, b.out.script_pubkey, b.height, b.coinbase)


def counter_total(name: str) -> float:
    samples = get_registry().snapshot().get(name, {"samples": []})["samples"]
    return sum(s["value"] for s in samples)


def by_result() -> dict:
    samples = get_registry().snapshot().get(
        "consensus_stream_blocks_total", {"samples": []})["samples"]
    return {s["labels"]["result"]: s["value"] for s in samples}


def run_stream(chain, depth, *, start=START, take=None, verifier=None):
    """(results, view, sig_cache, script_cache, verifier) of the stream
    under test; `take` closes the generator after that many results."""
    view = chain.native_view()
    verifier = verifier or TpuSecpVerifier()
    sig_cache, script_cache = SigCache(), ScriptExecutionCache()
    stream = connect_block_stream(
        chain.blocks, view, start, depth=depth, verifier=verifier,
        pow_limit=REGTEST_POW_LIMIT, sig_cache=sig_cache, script_cache=script_cache,
    )
    out = []
    for res in stream:
        out.append(res)
        if take is not None and len(out) == take:
            stream.close()
            break
    return out, view, sig_cache, script_cache, verifier


@pytest.fixture(scope="module")
def valid_chain():
    chain = Chain("stream-valid")
    return chain, reference_loop(chain)


# ---------------------------------------------------------------------------
# Guarantee 1: results and view equal the loop's, at every depth.


@pytest.mark.parametrize("depth", DEPTHS)
def test_stream_equals_the_loop(valid_chain, depth):
    chain, (want, pview) = valid_chain
    assert len(want) == N_BLOCKS and all(r.ok for r in want)
    assert max(chain.n_inputs) <= 7  # with the 2-of-3's pairings, under 16 lanes
    before, rolled = by_result(), counter_total("consensus_stream_rollbacks_total")
    got, nview, _, _, verifier = run_stream(chain, depth)
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want]
    assert_same_coins(chain, nview, pview)
    assert verifier._inflight.depth == 0
    after = by_result()
    assert after.get("ok", 0) - before.get("ok", 0) == N_BLOCKS
    assert after.get("reject", 0) == before.get("reject", 0)
    assert after.get("abandoned", 0) == before.get("abandoned", 0)
    assert counter_total("consensus_stream_rollbacks_total") == rolled


def test_the_flags_change_with_blocks_in_flight(valid_chain):
    chain, _ = valid_chain
    assert not height_to_flags(START + 1, extended=True) & VERIFY_CHECKSEQUENCEVERIFY
    assert height_to_flags(START + 2, extended=True) & VERIFY_CHECKSEQUENCEVERIFY
    seen = []
    real = validate._NativeConnect.begin

    def spy(self, speculate=False):
        seen.append((self.height, self.flags, speculate))
        return real(self, speculate)

    validate._NativeConnect.begin = spy
    try:
        got, *_ = run_stream(chain, 2)
    finally:
        validate._NativeConnect.begin = real
    assert all(r.ok for r in got)
    assert seen == [
        (START + k, height_to_flags(START + k, extended=True), True)
        for k in range(N_BLOCKS)
    ]


@pytest.mark.parametrize("depth", DEPTHS)
def test_a_script_that_the_new_flag_fails(valid_chain, depth):
    """The same blocks one height later: the version-1 spend of the
    CHECKSEQUENCEVERIFY script, a NOP at 419,327, is block 1 at 419,328
    and fails there, in finish, with a block begun behind it."""
    chain, _ = valid_chain
    want, pview = reference_loop(chain, start=START + 1)
    assert [r.ok for r in want] == [True, False]
    assert want[1].input_results[want[1].script_failures[0]].script_error == (
        ScriptError.UNSATISFIED_LOCKTIME)
    got, nview, _, _, verifier = run_stream(chain, depth, start=START + 1)
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want]
    assert_same_coins(chain, nview, pview)
    assert verifier._inflight.depth == 0


def test_connect_block_is_the_depth_one_case(valid_chain):
    chain, (want, pview) = valid_chain
    view = chain.native_view()
    verifier = TpuSecpVerifier()
    sig_cache, script_cache = SigCache(), ScriptExecutionCache()
    begun = []
    real = validate._NativeConnect.begin

    def spy(self, speculate=False):
        begun.append(speculate)
        return real(self, speculate)

    validate._NativeConnect.begin = spy
    try:
        got = [
            connect_block(raw, view, START + k, verifier=verifier,
                          pow_limit=REGTEST_POW_LIMIT, sig_cache=sig_cache,
                          script_cache=script_cache)
            for k, raw in enumerate(chain.blocks)
        ]
    finally:
        validate._NativeConnect.begin = real
    assert begun == [False] * N_BLOCKS  # the same halves, no speculation
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want]
    assert_same_coins(chain, view, pview)


def test_python_view_falls_back_to_the_loop(valid_chain):
    chain, (want, pview) = valid_chain
    view = chain.python_view()
    got = list(connect_block_stream(
        [Block.deserialize(raw) for raw in chain.blocks], view, START, depth=2,
        verifier=TpuSecpVerifier(), pow_limit=REGTEST_POW_LIMIT,
        sig_cache=SigCache(), script_cache=ScriptExecutionCache(),
    ))
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want]
    assert view._map.keys() == pview._map.keys()


# ---------------------------------------------------------------------------
# Guarantee 2: a failing block ends the stream and the view is rolled back.


@pytest.mark.parametrize("depth", DEPTHS)
def test_a_block_that_fails_where_it_is_begun(depth):
    chain = Chain("stream-missing", missing_input_block=3)
    want, pview = reference_loop(chain)
    assert [r.ok for r in want] == [True, True, True, False]
    assert want[3].reason == "bad-txns-inputs-missingorspent"
    before, rolled = by_result(), counter_total("consensus_stream_rollbacks_total")
    got, nview, sig_cache, script_cache, verifier = run_stream(chain, depth)
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want]
    assert_same_coins(chain, nview, pview)
    assert verifier._inflight.depth == 0
    after = by_result()
    assert after.get("ok", 0) - before.get("ok", 0) == 3
    assert after.get("reject", 0) - before.get("reject", 0) == 1
    # It was refused before anything of it was launched or applied, and it
    # put nothing into a cache: both hold what three blocks put there.
    assert counter_total("consensus_stream_rollbacks_total") == rolled
    assert len(script_cache) == sum(chain.n_inputs[:3])


@pytest.mark.parametrize("depth", DEPTHS)
def test_a_block_that_fails_where_it_is_finished(depth):
    chain = Chain("stream-badsig", bad_sig_block=2)
    want, pview = reference_loop(chain)
    assert [r.ok for r in want] == [True, True, False]
    assert want[2].reason == "block-validation-failed"
    assert want[2].script_failures == [0]
    before, rolled = by_result(), counter_total("consensus_stream_rollbacks_total")
    got, nview, sig_cache, script_cache, verifier = run_stream(chain, depth)
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want]
    assert_same_coins(chain, nview, pview)
    assert verifier._inflight.depth == 0
    # Blocks 3.. were begun behind it at depth 2 and 3 (block 3 spends an
    # output of block 2): abandoned, undone newest first, then block 2.
    behind = min(depth - 1, N_BLOCKS - 3)
    after = by_result()
    assert after.get("abandoned", 0) - before.get("abandoned", 0) == behind
    assert counter_total("consensus_stream_rollbacks_total") - rolled == behind + 1
    # Guarantee 4: the caches hold what the loop's hold, the passing inputs
    # of the blocks that were finished; an abandoned block put in nothing.
    assert len(script_cache) == sum(chain.n_inputs[:3]) - 1


# ---------------------------------------------------------------------------
# Guarantee 3: an early close.


@pytest.mark.parametrize("depth", DEPTHS)
def test_an_early_close_undoes_what_was_begun(valid_chain, depth):
    chain, (want, _) = valid_chain
    _, pview = reference_loop(chain, blocks=chain.blocks[:2])
    before, rolled = by_result(), counter_total("consensus_stream_rollbacks_total")
    got, nview, _, script_cache, verifier = run_stream(chain, depth, take=2)
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want[:2]]
    assert_same_coins(chain, nview, pview)
    assert verifier._inflight.depth == 0
    after = by_result()
    assert after.get("abandoned", 0) - before.get("abandoned", 0) == depth - 1
    assert counter_total("consensus_stream_rollbacks_total") - rolled == depth - 1
    assert len(script_cache) == sum(chain.n_inputs[:2])


# ---------------------------------------------------------------------------
# Guarantee 5: a fault at the dispatch seam during a stream.


@pytest.mark.parametrize("site,kind", [
    ("jax_backend.dispatch", "raise"), ("jax_backend.verdict", "flip"),
])
def test_a_fault_during_a_stream_changes_no_verdict(valid_chain, site, kind):
    chain, (want, pview) = valid_chain
    retries = counter_total("consensus_resilience_retries_total")
    with inject(FaultPlan([FaultSpec(site, kind, count=1)]), seed=7) as inj:
        got, nview, _, _, verifier = run_stream(chain, 2)
    assert inj.fired == {(site, kind): 1}
    assert [as_tuple(r) for r in got] == [as_tuple(r) for r in want]
    assert_same_coins(chain, nview, pview)
    assert verifier._inflight.depth == 0
    assert counter_total("consensus_resilience_retries_total") > retries


# ---------------------------------------------------------------------------
# The undo record.


def _parsed(chain, k):
    return native_bridge.NativeBlock(chain.blocks[k])


def test_apply_then_undo_is_the_identity(valid_chain):
    """Also for a block that spends a coin created in the same block (tx 2
    of every block of the chain does)."""
    chain, _ = valid_chain
    view = chain.native_view()
    size, digest = len(view), view.digest()
    blk = _parsed(chain, 0)
    assert not blk.check(True, REGTEST_POW_LIMIT)
    undo = view.apply_block(blk, START, undo=True)
    assert len(undo) == chain.n_inputs[0]  # the in-block coin among them
    assert (len(view), view.digest()) != (size, digest)
    view.undo_block(blk, undo)
    assert (len(view), view.digest()) == (size, digest)
    for (txid, n, amount, height, cb, spk) in chain.coins:
        coin = view.get(OutPoint(txid, n))
        assert (coin.out.value, coin.out.script_pubkey, coin.height, coin.coinbase) == (
            amount, spk, height, cb)
    # The record keeps its coins: it puts back any view that stands where
    # the apply left one, and a second undo changes nothing.
    view.undo_block(blk, undo)
    assert (len(view), view.digest()) == (size, digest)
    twin = chain.native_view()
    twin.apply_block(blk, START)
    twin.undo_block(blk, undo)
    assert (len(twin), twin.digest()) == (size, digest)
    with pytest.raises(ValueError):
        view.undo_block(native_bridge.NativeBlock(chain.blocks[1]), undo)


def test_undo_newest_first_over_dependent_blocks(valid_chain):
    chain, _ = valid_chain
    view = chain.native_view()
    marks, undos, blks = [], [], []
    for k in range(3):
        marks.append((len(view), view.digest()))
        blks.append(_parsed(chain, k))
        undos.append(view.apply_block(blks[k], START + k, undo=True))
    plain = chain.native_view()
    for k in range(3):
        plain.apply_block(_parsed(chain, k), START + k)
    assert (len(view), view.digest()) == (len(plain), plain.digest())
    for k in (2, 1, 0):
        view.undo_block(blks[k], undos[k])
        assert (len(view), view.digest()) == marks[k]


def test_undo_restores_a_coin_the_block_overwrote(valid_chain):
    """Accounting's BIP30 scan refuses such a block; apply is reachable
    alone, and its inverse has to be exact there too."""
    chain, _ = valid_chain
    view = chain.native_view()
    blk = _parsed(chain, 0)
    clash = OutPoint(blk.txid(1), 0)
    view.add(clash, Coin(TxOut(5, b"\x51"), 7, True))
    size, digest = len(view), view.digest()
    undo = view.apply_block(blk, START, undo=True)
    assert view.get(clash).out.value != 5
    view.undo_block(blk, undo)
    assert (len(view), view.digest()) == (size, digest)
    coin = view.get(clash)
    assert (coin.out.value, coin.out.script_pubkey, coin.height, coin.coinbase) == (
        5, b"\x51", 7, True)
