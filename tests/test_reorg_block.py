"""`disconnect_block`, `ConnectResult.undo` and `want_undo`, three ways.

A node at the tip follows a reorganisation: it takes the blocks of its own
branch off the view with the records their connects handed out, newest
first, and connects the blocks of the branch that won. Here both branches
are seeded and small, and every step runs on a `NativeCoinsView`, on a
Python `CoinsView` and on the plain reference (`benchmarks/harness/
reorgref.py`: a dict, a parser of its own, Core 0.21's `DisconnectBlock`
step by step), which have to hold the same coins afterwards, coin for coin.

A block of a branch has two transactions, four or five inputs and seven or
eight curve checks (the rungs `warm_kernel` has warmed): one that spends a
P2WPKH and a P2PKH coin and, after the branch's first block, a P2WPKH
output the block before paid forward (so order matters), and one, which
both branches hold, that spends a P2WSH 2-of-3 and a taproot key-path coin.
"""

import hashlib

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from benchmarks.generators.fork import spend_tx
from benchmarks.harness import reorgref, signer
from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.block import Block
from bitcoinconsensus_tpu.core.tx import OutPoint, TxOut
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
from bitcoinconsensus_tpu.models import sigcache
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import (
    BlockUndo,
    Coin,
    CoinsView,
    connect_block,
    connect_block_stream,
    disconnect_block,
)
from bitcoinconsensus_tpu.obs import add_sink, get_registry, remove_sink

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: the 8- and 16-lane rungs
]

FORK = 709_999  # the last block both branches hold
FEE = 1000
VIEWS = ("native", "python")
# How the blocks of a branch are connected: one `connect_block` a block, or
# one stream at a depth.
WAYS = ("connect", "stream-1", "stream-2", "stream-3")


class Branches:
    """Branch A of `depth` blocks and branch B of `depth + 1` over one fork
    point, as raw blocks from height `FORK + 1`, and the coins both spend
    from outside themselves. `corrupt_b` flips one signature bit in the
    witness of that block of B (the block behind it still finds the output
    it spends: a witness is not in a txid)."""

    def __init__(self, seed: str, depth: int, corrupt_b=None):
        self.coins = []
        self.depth = depth
        n_funded = 0

        def fund(kind):
            nonlocal n_funded
            i = n_funded
            n_funded += 1
            f = signer.FundedOutput(
                OutPoint(hashlib.sha256(f"{seed}/op/{i}".encode()).digest(), i),
                signer.Wallet(f"{seed}/{i}", kind), 1_000_000 + i)
            self.coins.append((f.outpoint.hash, f.outpoint.n, f.amount, 1, False, f.wallet.spk))
            return f

        def branch(name, n_blocks, shared, corrupt=None):
            blocks, carried = [], []
            for k in range(n_blocks):
                pay = signer.Wallet(f"{seed}/{name}/{k}/forward", "p2wpkh")
                first = spend_tx([fund("p2wpkh"), fund("p2pkh")] + carried, FEE, pay,
                                 corrupt_input=0 if k == corrupt else None)
                carried = [signer.FundedOutput(OutPoint(first.txid, 1), pay, first.vout[1].value)]
                both = shared[k] if k < len(shared) else spend_tx(
                    [fund("p2wsh_multisig"), fund("p2tr")], FEE)
                if k >= len(shared):
                    shared.append(both)
                blocks.append(signer.build_block(
                    [first, both], FORK + 1 + k, fees=2 * FEE).serialize())
            return blocks

        shared = []
        self.a = branch("a", depth, shared)
        self.b = branch("b", depth + 1, shared, corrupt=corrupt_b)

    def view(self, kind: str):
        if kind == "native":
            view = native_bridge.NativeCoinsView()
            view.add_coins_batch(self.coins)
            return view
        view = CoinsView()
        for txid, n, amount, height, cb, spk in self.coins:
            view.add(OutPoint(txid, n), Coin(TxOut(amount, spk), height, cb))
        return view

    def reference(self) -> reorgref.ReorgRef:
        return reorgref.ReorgRef(self.coins)


@pytest.fixture(scope="module")
def branches():
    made = {}

    def get(depth: int, corrupt_b=None) -> Branches:
        key = (depth, corrupt_b)
        if key not in made:
            made[key] = Branches(f"test_reorg_block/{depth}", depth, corrupt_b)
        return made[key]

    return get


@pytest.fixture(scope="module")
def verifier():
    return TpuSecpVerifier()


def connect(way, blocks, view, first_height, verifier, caches=None, want_undo=True):
    """The `ConnectResult`s of `blocks` connected the given way."""
    sig, script = caches or (SigCache(), ScriptExecutionCache())
    if isinstance(view, CoinsView):  # the Python pipeline takes `Block`s
        blocks = [Block.deserialize(raw) for raw in blocks]
    common = dict(verifier=verifier, pow_limit=signer.REGTEST_POW_LIMIT,
                  sig_cache=sig, script_cache=script, want_undo=want_undo)
    if way == "connect":
        out = []
        for k, raw in enumerate(blocks):
            out.append(connect_block(raw, view, first_height + k, **common))
            if not out[-1].ok:
                break
        return out
    return list(connect_block_stream(blocks, view, first_height,
                                     depth=int(way.split("-")[1]), **common))


def as_tuple(res):
    inputs = None
    if res.input_results is not None:
        inputs = [(r.ok, r.error, r.script_error) for r in res.input_results]
    return (res.ok, res.reason, res.fees, res.sigop_cost, inputs)


def state(view):
    """What two views of one kind agree in exactly when they hold the same
    coins."""
    if isinstance(view, CoinsView):
        return {k: (c.out.value, c.out.script_pubkey, c.height, c.coinbase)
                for k, c in view._map.items()}
    return len(view), view.digest()


def counter(name: str, label: str) -> dict:
    samples = get_registry().snapshot().get(name, {"samples": []})["samples"]
    return {s["labels"][label]: s["value"] for s in samples}


def rollbacks() -> float:
    samples = get_registry().snapshot()["consensus_stream_rollbacks_total"]["samples"]
    return sum(s["value"] for s in samples)


def rose(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


# -- a connect with a record, taken back ---------------------------------------


@pytest.mark.parametrize("way", WAYS)
@pytest.mark.parametrize("kind", VIEWS)
def test_connect_with_a_record_then_disconnect_restores_the_view(branches, verifier, kind, way):
    br = branches(2)
    view = br.view(kind)
    before = state(view)
    results = connect(way, br.a, view, FORK + 1, verifier)
    assert [r.ok for r in results] == [True, True]
    assert all(r.undo is not None and len(r.undo) == 4 + k for k, r in enumerate(results))
    assert state(view) != before
    for k in (1, 0):  # newest first
        res = disconnect_block(br.a[k], view, results[k].undo, FORK + 1 + k)
        assert (res.ok, res.reason, res.restored, res.removed) == (True, "ok", 4 + k, 5)
    assert state(view) == before


@pytest.mark.parametrize("way", ("connect", "stream-2"))
@pytest.mark.parametrize("kind", VIEWS)
def test_without_want_undo_a_result_carries_no_record(branches, verifier, kind, way):
    br = branches(2)
    results = connect(way, br.a, br.view(kind), FORK + 1, verifier, want_undo=False)
    assert [r.ok for r in results] == [True, True]
    assert all(r.undo is None for r in results)


@pytest.mark.parametrize("way", ("connect", "stream-2"))
@pytest.mark.parametrize("kind", VIEWS)
def test_want_undo_changes_no_verdict_fee_or_sigop_cost(branches, verifier, kind, way):
    """Sound blocks and a block with a flipped signature, with and without
    the record: every field of every result but `undo` is the same."""
    for br, oks in ((branches(2), [True, True, True]), (branches(2, corrupt_b=1), [True, False])):
        plain = connect(way, br.b, br.view(kind), FORK + 1, verifier, want_undo=False)
        kept = connect(way, br.b, br.view(kind), FORK + 1, verifier, want_undo=True)
        assert [r.ok for r in plain] == oks
        assert [as_tuple(r) for r in kept] == [as_tuple(r) for r in plain]
        assert [r.undo is not None for r in kept] == oks  # a failed block hands out none
        assert plain[0].sigop_cost > 0 and plain[0].fees == 2 * FEE


# -- reorganisations, against the plain reference ------------------------------


@pytest.mark.parametrize("way", WAYS)
@pytest.mark.parametrize("depth", (1, 2, 3))
@pytest.mark.parametrize("kind", VIEWS)
def test_a_reorganisation_leaves_the_reference_s_coins(branches, verifier, kind, depth, way):
    """A connected, A disconnected newest first, B connected on the caches A
    left warm, then all the way back: after every leg the view holds the
    reference's coins, coin for coin."""
    br = branches(depth)
    view, ref = br.view(kind), br.reference()
    at_fork = state(view)
    caches = (SigCache(), ScriptExecutionCache())
    on_a = connect(way, br.a, view, FORK + 1, verifier, caches)
    assert all(r.ok for r in on_a) and len(on_a) == depth
    ref_a = [ref.connect(raw, FORK + 1 + k) for k, raw in enumerate(br.a)]
    assert ref.differences(view, 0) == []
    at_tip_a = state(view)

    for k in reversed(range(depth)):
        assert disconnect_block(br.a[k], view, on_a[k].undo, FORK + 1 + k).ok
        assert ref.disconnect(br.a[k], ref_a[k], FORK + 1 + k) == "ok"
    assert state(view) == at_fork and ref.differences(view, 0) == []

    hits = counter("consensus_cache_hits_total", "cache").get("script", 0)
    on_b = connect(way, br.b, view, FORK + 1, verifier, caches)
    assert all(r.ok for r in on_b) and len(on_b) == depth + 1
    # the transactions both branches hold are answered by the script cache:
    # two inputs of each of B's first `depth` blocks
    assert counter("consensus_cache_hits_total", "cache").get("script", 0) - hits == 2 * depth
    ref_b = [ref.connect(raw, FORK + 1 + k) for k, raw in enumerate(br.b)]
    assert ref.differences(view, 0) == []

    for k in reversed(range(depth + 1)):
        assert disconnect_block(br.b[k], view, on_b[k].undo, FORK + 1 + k).ok
        assert ref.disconnect(br.b[k], ref_b[k], FORK + 1 + k) == "ok"
    assert state(view) == at_fork
    again = connect(way, br.a, view, FORK + 1, verifier)
    assert all(r.ok for r in again) and state(view) == at_tip_a


# -- what the operator refuses, the view untouched -----------------------------


# Each offer: the block and the record of branch A that are handed in, the
# height, Core's outcome, and the disconnects that are due first.
OFFERS = {
    # A2's first transaction has one input more than A1's
    "another_blocks_record": dict(block=1, record=0, height=FORK + 2, want="failed", first=()),
    # A2 spends the output A1 paid forward
    "out_of_order": dict(block=0, record=0, height=FORK + 1, want="unclean", first=()),
    # A2's outputs are gone once A2 is disconnected
    "a_second_time": dict(block=1, record=1, height=FORK + 2, want="unclean", first=(1,)),
    # A2's outputs are not as a block of that height would have made them
    "another_height": dict(block=1, record=1, height=FORK + 3, want="unclean", first=()),
}


@pytest.mark.parametrize("offer", sorted(OFFERS))
@pytest.mark.parametrize("kind", VIEWS)
def test_a_refused_disconnect_leaves_the_view_untouched(branches, verifier, kind, offer):
    o = OFFERS[offer]
    br = branches(2)
    view, ref = br.view(kind), br.reference()
    on_a = connect("stream-2", br.a, view, FORK + 1, verifier)
    ref_a = [ref.connect(raw, FORK + 1 + k) for k, raw in enumerate(br.a)]
    for k in o["first"]:
        assert disconnect_block(br.a[k], view, on_a[k].undo, FORK + 1 + k).ok
        assert ref.disconnect(br.a[k], ref_a[k], FORK + 1 + k) == "ok"
    before = state(view)
    ended = counter("consensus_blocks_disconnected_total", "result")
    moved = counter("consensus_undo_coins_total", "what")
    got = disconnect_block(br.a[o["block"]], view, on_a[o["record"]].undo, o["height"])
    assert (got.ok, got.reason, got.restored, got.removed) == (False, o["want"], 0, 0)
    assert ref.disconnect(br.a[o["block"]], ref_a[o["record"]], o["height"]) == o["want"]
    assert state(view) == before and ref.differences(view, 0) == []
    assert rose(ended, counter("consensus_blocks_disconnected_total", "result")) == {o["want"]: 1}
    assert counter("consensus_undo_coins_total", "what") == moved  # no coin counted


@pytest.mark.parametrize("depth", (2, 3))
@pytest.mark.parametrize("kind", VIEWS)
def test_the_corrupted_branch_is_left_by_the_record_its_stream_handed_out(
        branches, verifier, kind, depth):
    """B's second block has one flipped signature and the block behind it
    spends its output: B1 ok, B2 rejected for exactly its victim, the end,
    the view at fork + B1 (the stream's rollbacks left B1's record sound);
    then B1 disconnected by that record, and A connected again."""
    br = branches(2, corrupt_b=1)
    view, ref = br.view(kind), br.reference()
    caches = (SigCache(), ScriptExecutionCache())
    on_a = connect(f"stream-{depth}", br.a, view, FORK + 1, verifier, caches)
    at_tip_a = state(view)
    for k in (1, 0):
        assert disconnect_block(br.a[k], view, on_a[k].undo, FORK + 1 + k).ok
    at_fork = state(view)
    rolled = rollbacks()
    on_b = connect(f"stream-{depth}", br.b, view, FORK + 1, verifier, caches)
    assert [r.ok for r in on_b] == [True, False]
    assert on_b[1].reason == "block-validation-failed" and on_b[1].script_failures == [0]
    assert on_b[0].undo is not None and on_b[1].undo is None
    if kind == "native":  # B2's own apply, and that of B3 begun behind it
        assert rollbacks() - rolled == 2
    ref_b1 = ref.connect(br.b[0], FORK + 1)
    assert ref.differences(view, 0) == []
    assert disconnect_block(br.b[0], view, on_b[0].undo, FORK + 1).ok
    assert ref.disconnect(br.b[0], ref_b1, FORK + 1) == "ok"
    assert state(view) == at_fork
    back = connect(f"stream-{depth}", br.a, view, FORK + 1, verifier)
    assert all(r.ok for r in back) and state(view) == at_tip_a


# -- what a disconnect touches, and what it counts -----------------------------


@pytest.mark.parametrize("kind", VIEWS)
def test_a_disconnect_consults_and_changes_no_cache(branches, verifier, kind, monkeypatch):
    br = branches(1)
    view = br.view(kind)
    caches = (SigCache(), ScriptExecutionCache())
    (res,) = connect("connect", br.a, view, FORK + 1, verifier, caches)
    held = [len(c) for c in caches]

    def touched(*a, **k):
        raise AssertionError("a disconnect reached a cache")

    for name in ("contains_keys", "add_keys", "contains_key", "add_key"):
        monkeypatch.setattr(sigcache._SaltedLRU, name, touched)
    assert disconnect_block(br.a[0], view, res.undo, FORK + 1, verifier=verifier).ok
    assert [len(c) for c in caches] == held


class _Records:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)


@pytest.mark.parametrize("kind", VIEWS)
def test_the_span_and_the_counters_rise_by_the_reference_s_counts(branches, verifier, kind):
    br = branches(2)
    view, ref = br.view(kind), br.reference()
    on_a = connect("stream-2", br.a, view, FORK + 1, verifier)
    ref_a = [ref.connect(raw, FORK + 1 + k) for k, raw in enumerate(br.a)]
    # by the reference: what A2's disconnect puts back and takes out
    restored = sum(len(tx) for tx in ref_a[1])
    removed = sum(len(tx["vout"]) for tx in reorgref.parse_block(br.a[1]))
    assert (restored, removed) == (5, 5)

    ended = counter("consensus_blocks_disconnected_total", "result")
    moved = counter("consensus_undo_coins_total", "what")
    probes = counter("consensus_coin_probes_total", "table")
    verifier.phases.reset()
    sink = _Records()
    add_sink(sink)
    try:
        res = disconnect_block(br.a[1], view, on_a[1].undo, FORK + 2, verifier=verifier)
    finally:
        remove_sink(sink)
    assert (res.ok, res.restored, res.removed) == (True, restored, removed)
    assert rose(ended, counter("consensus_blocks_disconnected_total", "result")) == {"ok": 1}
    assert rose(moved, counter("consensus_undo_coins_total", "what")) == {
        "restored": restored, "removed": removed}
    # one probe of the view a coin moved, on the native view (the Python
    # view counts none)
    want_probes = {"undo": restored + removed} if kind == "native" else {}
    assert rose(probes, counter("consensus_coin_probes_total", "table")) == want_probes
    span, = [r for r in sink.records if r["name"] == "block.disconnect"]
    assert span["attrs"] == {"height": FORK + 2, "result": "ok",
                             "restored": restored, "removed": removed}
    children = {r["name"] for r in sink.records if r.get("parent_id") == span["span_id"]}
    want = {"verifier.parse", "verifier.undo_check", "verifier.undo"}
    assert children == want | ({"verifier.block_free"} if kind == "native" else set())
    report = verifier.phases.report()
    assert all(report[n[9:]]["calls"] == 1 for n in children)


# -- the record and the bridge ---------------------------------------------------


def test_the_record_outlives_the_parsed_block_and_is_not_consumed(branches, verifier):
    br = branches(1)
    view = br.view("native")
    before = state(view)
    nblk = native_bridge.NativeBlock(br.a[0])
    record = view.apply_block(nblk, FORK + 1, undo=True)
    applied = state(view)
    del nblk
    for _ in range(2):  # any parse of the same bytes, as often as asked
        again = native_bridge.NativeBlock(br.a[0])
        assert record.matches(again) and len(record) == 4
        assert view.disconnect_block(again, record, FORK + 1) == ("ok", 9, 4, 5)
        assert state(view) == before
        view.apply_block(again, FORK + 1)
        assert state(view) == applied
    other = native_bridge.NativeBlock(br.b[0])
    assert not record.matches(other)
    assert view.disconnect_block(other, record, FORK + 1) == ("failed", 0, 0, 0)
    assert state(view) == applied


def test_a_view_takes_its_own_kind_of_record(branches, verifier):
    br = branches(1)
    (res,) = connect("connect", br.a, br.view("python"), FORK + 1, verifier)
    assert isinstance(res.undo, BlockUndo) and res.undo.spent[0] == []  # the coinbase spends none
    with pytest.raises(TypeError):
        disconnect_block(br.a[0], br.view("native"), res.undo, FORK + 1)
    (res,) = connect("connect", br.a, br.view("native"), FORK + 1, verifier)
    assert isinstance(res.undo, native_bridge.NativeBlockUndo)
    with pytest.raises(TypeError):
        disconnect_block(Block.deserialize(br.a[0]), br.view("python"), res.undo, FORK + 1)


@pytest.mark.parametrize("n_threads", [1, 4])
def test_a_large_view_s_digest_is_the_xor_of_its_coins_hashes(n_threads):
    """The digest is made over cuts of the map's buckets, by one worker a
    65,536 coins up to the `n_threads` the native call is handed (here one,
    and two of four; `digest()` hands it the host's cores): the same 32
    bytes as the plain XOR."""
    import numpy as np

    n = 140_000
    rng = np.random.Generator(np.random.PCG64(48))
    txids = rng.bytes(32 * n)
    values = rng.integers(1, 10**9, n, dtype=np.int64)
    spks = [bytes([0x51 + (i % 3)]) * (1 + i % 40) for i in range(n)]
    view = native_bridge.NativeCoinsView()
    view.add_coins_batch([
        (txids[32 * i : 32 * i + 32], i & 7, int(values[i]), 1 + (i % 1000), i % 5 == 0, spks[i])
        for i in range(n)])
    want = 0
    for i in range(n):
        coin = (txids[32 * i : 32 * i + 32] + (i & 7).to_bytes(4, "little")
                + int(values[i]).to_bytes(8, "little") + (1 + (i % 1000)).to_bytes(4, "little")
                + bytes([i % 5 == 0]) + spks[i])
        want ^= int.from_bytes(hashlib.sha256(coin).digest(), "big")
    out = np.zeros(32, np.uint8)
    native_bridge.lib().nat_view_digest(view._ptr, native_bridge._u8p(out), n_threads)
    assert len(view) == n and out.tobytes() == want.to_bytes(32, "big")
    assert view.clone().digest() == view.digest() == out.tobytes()
