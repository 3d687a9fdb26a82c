"""m-of-n CHECKMULTISIG with m > 1 through `connect_block`: the band of
pairings the index path pre-records, and the walk that reads it.

`benchmarks/configs/worst-block-multisig20.json` runs 2,750 8-of-20 inputs
on the chip (286,000 lanes in 35 dispatches where Core's walk verifies
55,000 pairings). Here the same code runs small on the CPU, on the 16-lane
rung. **The cross**: (m, n) in {(2, 3), (3, 5), (5, 8)} x three placements
of the signers x five twins (sound; one bit of the first-pushed signature
flipped; one bit of a middle signature flipped; two adjacent signatures
swapped, each valid for a listed key; one signature by a key not in the
list), the five twins of a (shape, placement) as the inputs of one block on
a native view. **The size shape**: a block of 8-of-20 inputs signed by the
eight first-pushed keys, sound and with one twin, several chunks a round.
Each is compared three ways: the program, the executable spec
(`spec_connect`: the pure-Python interpreter over `secp_host`) and the
plain reference's walk (`harness/msigref.py` over `sigopref.multisig_walk`
and `ecverify.py`, which share nothing with either).
"""

import hashlib

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from benchmarks.generators import multisigblock
from benchmarks.harness import ec, msigner, msigref, signer, sigopref
from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.core.sighash import SIGHASH_ALL, bip143_sighash
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import Coin, CoinsView, connect_block
from bitcoinconsensus_tpu.obs import add_sink, remove_sink

from test_native_block import HEIGHT, to_native_view
from test_worst_block import _total, same_result, spec_connect, to_python_copy

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: the 16-lane rung
]

AMOUNT = 1_000_000
SHAPES = [(2, 3), (3, 5), (5, 8)]
PLACEMENTS = {
    "first-pushed": lambda m, n: list(range(m)),
    "last-pushed": lambda m, n: list(range(n - m, n)),
    "spread": lambda m, n: [round(i * (n - 1) / (m - 1)) for i in range(m)],
}
OUTSIDE = "outside-key"
TWINS = ["sound", *multisigblock.corruptions(2), OUTSIDE]

COUNTERS = (
    "consensus_dispatch_total", "consensus_dispatch_lanes_total",
    "consensus_multisig_spec_pairings_total", "consensus_multisig_walk_pairings_total",
    "consensus_fixpoint_reinterpreted_inputs_total", "consensus_fixpoint_rounds",
    "consensus_exact_fallback_total",
)


def _read() -> dict:
    return {n: _total(n) for n in COUNTERS}


class _Records:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)


class Spend:
    """One P2WSH m-of-n input: its coin, and who signed which signature
    after the twin was made (a key's push position, None for a signature
    that verifies against no listed key)."""

    def __init__(self, tag: str, m: int, n: int, signers, twin: str):
        self.m, self.n, self.twin = m, n, twin
        (self.base,) = msigner.run_bases(tag, 1, n + 1)  # one key more: the outsider's
        (pubs,) = msigner.key_runs([self.base], n)
        self.coin = msigner.MultisigCoin(self.base, pubs, signers)
        self.outpoint = OutPoint(hashlib.sha256(f"{tag}/op".encode()).digest(), 3)
        self.signed_by = list(signers)

    def sign(self, tx: Tx, i: int) -> None:
        self.coin.sign_input(tx, i, AMOUNT)
        if self.twin == "sound":
            return
        dummy, *sigs, script = tx.vin[i].witness
        by, mid = self.signed_by, self.m // 2
        if self.twin == OUTSIDE:  # the last-pushed signature, which the walk tries first
            digest = bip143_sighash(script, tx, i, SIGHASH_ALL, AMOUNT)
            sigs[-1] = ec.sign_ecdsa(self.base + self.n, digest) + bytes([SIGHASH_ALL])
            by[-1] = None
        else:
            sigs = multisigblock.corruptions(self.m)[self.twin](sigs)
            if self.twin == "first-signature":
                by[0] = None
            elif self.twin == "middle-signature":
                by[mid] = None
            else:
                by[mid - 1], by[mid] = by[mid], by[mid - 1]
        tx.vin[i].witness = [dummy] + sigs + [script]
        tx.invalidate_caches()

    def true_lanes(self) -> int:
        """Pairings of the pre-recorded band (key - signature position in
        [0, n - m]) that verify, by construction."""
        return sum(k is not None and 0 <= k - s <= self.n - self.m
                   for s, k in enumerate(self.signed_by))


def build(spends, sizes):
    """(block, per-tx (raw, outs), python coins): `spends` cut into
    transactions of `sizes` inputs."""
    coins = CoinsView()
    for sp in spends:
        coins.add(sp.outpoint, Coin(TxOut(AMOUNT, sp.coin.spk), height=1, coinbase=False))
    txs, records, at = [], [], 0
    for size in sizes:
        cut = spends[at : at + size]
        tx = Tx(version=2, vin=[TxIn(sp.outpoint) for sp in cut],
                vout=[TxOut(AMOUNT * size - 1000, b"\x00\x14" + bytes([size]) * 20)], locktime=0)
        for i, sp in enumerate(cut):
            sp.sign(tx, i)
        txs.append(tx)
        records.append((tx.serialize(), [(AMOUNT, sp.coin.spk) for sp in cut]))
        at += size
    return signer.build_block(txs, HEIGHT, fees=1000 * len(txs)), records, coins


def reference(records):
    """Every input through the plain reference, in block order."""
    out = []
    for raw, outs in records:
        tx = sigopref.parse_tx(raw)
        out += [msigref.verify_input(tx, i, outs) for i in range(len(tx.vin))]
    return out


def connect(block, coins):
    """The program's connect on a native view, with what it counted, the
    signature cache it filled and its `block.connect` span record."""
    view = to_native_view(coins)
    digest = view.digest()
    sig_cache, sink = SigCache(), _Records()
    verifier = TpuSecpVerifier(min_batch=16, chunk=16)
    before = _read()
    add_sink(sink)
    try:
        res = connect_block(block.serialize(), view, HEIGHT, pow_limit=signer.REGTEST_POW_LIMIT,
                            verifier=verifier, sig_cache=sig_cache,
                            script_cache=ScriptExecutionCache())
    finally:
        remove_sink(sink)
    rose = {k[len("consensus_"):]: v - before[k] for k, v in _read().items()}
    (span,) = [r for r in sink.records if r["name"] == "block.connect"]
    return {"res": res, "rose": rose, "cached": len(sig_cache), "span": span["attrs"],
            "untouched": len(view) == len(coins._map) and view.digest() == digest,
            "verifier": verifier}


def verdict(r) -> tuple:
    return bool(r.ok), "OK" if r.ok else ScriptError(int(r.script_error)).name


def held_to_the_reference(got, spends, refs, cost):
    """What every connect here owes the plain reference and construction."""
    res, rose = got["res"], got["rose"]
    lanes = sum(sp.m * (sp.n - sp.m + 1) for sp in spends)
    assert res.sigop_cost == cost
    assert [verdict(r) for r in res.input_results] == [(v.ok, v.error) for v in refs]
    assert rose["dispatch_lanes_total"] == lanes
    assert rose["multisig_spec_pairings_total"] == lanes
    assert rose["multisig_walk_pairings_total"] == sum(len(v.tried) for v in refs)
    assert rose["exact_fallback_total"] == 0
    for sp, v in zip(spends, refs):  # the walk never leaves the band that was dispatched
        assert all(0 <= k - s <= sp.n - sp.m for s, k in v.tried)
    assert got["cached"] == sum(sp.true_lanes() for sp in spends)
    assert got["span"]["lanes_ecdsa"] == got["span"]["spec_pairings"] == lanes
    assert got["span"]["walk_pairings"] == rose["multisig_walk_pairings_total"]


# -- the cross -----------------------------------------------------------------

@pytest.fixture(scope="module")
def crossed():
    """Per (shape, placement): the block of the five twins, connected once
    by the program and once by the executable spec, and walked once by the
    plain reference; read by its five cases and its block's own test."""
    made = {}

    def get(m: int, n: int, placement: str) -> dict:
        key = (m, n, placement)
        if key not in made:
            signers = PLACEMENTS[placement](m, n)
            assert len(set(signers)) == m and signers == sorted(signers) and signers[-1] < n
            spends = [Spend(f"multisig/{m}of{n}/{placement}/{twin}", m, n, signers, twin)
                      for twin in TWINS]
            block, records, coins = build(spends, [2, 3])
            made[key] = {
                "block": block, "spends": spends, "records": records, "refs": reference(records),
                "spec": spec_connect(block, to_python_copy(coins)),
                "got": connect(block, coins),
            }
        return made[key]

    return get


@pytest.mark.parametrize("twin", TWINS)
@pytest.mark.parametrize("placement", list(PLACEMENTS))
@pytest.mark.parametrize("m,n", SHAPES)
def test_a_twin_ends_the_same_three_ways(crossed, m, n, placement, twin):
    b = crossed(m, n, placement)
    i = TWINS.index(twin)
    want = (True, "OK") if twin == "sound" else (False, "EVAL_FALSE")
    ref = b["refs"][i]
    assert verdict(b["got"]["res"].input_results[i]) == want
    assert verdict(b["spec"].input_results[i]) == want
    assert (ref.ok, ref.error) == want
    # Core's walk: at most one pairing a key, every one inside the band
    assert 1 <= len(ref.tried) <= n
    assert all(0 <= k - s <= n - m for s, k in ref.tried)
    if twin == "sound":  # every key from the last-pushed down to the first signer's, once
        assert len(ref.tried) == n - PLACEMENTS[placement](m, n)[0]
        assert b["spends"][i].true_lanes() == m


@pytest.mark.parametrize("placement", list(PLACEMENTS))
@pytest.mark.parametrize("m,n", SHAPES)
def test_a_block_of_the_five_twins_is_rejected_for_its_four_victims(crossed, m, n, placement):
    b = crossed(m, n, placement)
    got, res = b["got"], b["got"]["res"]
    same_result(res, b["spec"])
    assert not res.ok and res.reason == "block-validation-failed"
    assert res.script_failures == [1, 2, 3, 4]
    assert got["untouched"]
    cost = sigopref.block_sigop_cost(
        sigopref.parse_tx(b["block"].vtx[0].serialize()),
        [(sigopref.parse_tx(raw), outs) for raw, outs in b["records"]])
    assert cost == 5 * n  # BIP 141: a witness CHECKMULTISIG counts its n
    held_to_the_reference(got, b["spends"], b["refs"], cost)
    assert got["rose"]["dispatch_total"] == -(-5 * m * (n - m + 1) // 15)


# -- the size shape: 8-of-20, several chunks a round ----------------------------------

SIZE_M, SIZE_N, SIZE_INPUTS, SIZE_VICTIM = 8, 20, 3, 1
SIZE_LANES = SIZE_INPUTS * SIZE_M * (SIZE_N - SIZE_M + 1)  # 312: 21 dispatches of the 16-lane rung


@pytest.mark.parametrize("twin", ["sound", "middle-signature"])
def test_a_block_of_8_of_20_takes_several_chunks_a_round(twin):
    spends = [Spend(f"multisig/size/{twin}/{i}", SIZE_M, SIZE_N, range(SIZE_M),
                    twin if i == SIZE_VICTIM else "sound") for i in range(SIZE_INPUTS)]
    block, records, coins = build(spends, [1, 2])
    refs = reference(records)
    spec = spec_connect(block, to_python_copy(coins))
    got = connect(block, coins)
    res, rose = got["res"], got["rose"]
    same_result(res, spec)
    cost = sigopref.block_sigop_cost(
        sigopref.parse_tx(block.vtx[0].serialize()),
        [(sigopref.parse_tx(raw), outs) for raw, outs in records])
    assert cost == SIZE_INPUTS * SIZE_N
    held_to_the_reference(got, spends, refs, cost)
    # 104 lanes an input where the walk of a sound one tries 20
    assert rose["dispatch_lanes_total"] == SIZE_LANES
    assert rose["dispatch_total"] == -(-SIZE_LANES // 15) == 21
    assert [len(v.tried) for v in refs if v.ok] == [SIZE_N] * sum(v.ok for v in refs)
    # round one guesses the last-pushed key for the last signature and is
    # wrong for every input; round two launches nothing
    assert rose["fixpoint_rounds"] == 2
    assert rose["fixpoint_reinterpreted_inputs_total"] == SIZE_INPUTS
    # more chunks than the queue is deep: the rest wait in `backpressure`
    phases = got["verifier"].phases.report()
    assert phases["dispatch"]["calls"] == 21
    assert phases["backpressure"]["calls"] == 21 - got["verifier"]._inflight.max_depth
    assert got["verifier"]._inflight.depth == 0
    if twin == "sound":
        assert res.ok and all(v.ok for v in refs)
        assert got["cached"] == SIZE_INPUTS * SIZE_M
        assert not got["untouched"]  # the inputs' coins went, the outputs' came
    else:
        assert not res.ok and res.script_failures == [SIZE_VICTIM]
        assert (refs[SIZE_VICTIM].ok, refs[SIZE_VICTIM].error) == (False, "EVAL_FALSE")
        assert got["cached"] == SIZE_INPUTS * SIZE_M - 1
        assert got["untouched"]


# -- a retired store is parked for the next session ------------------------------------

@pytest.fixture(scope="module")
def round_one():
    """() -> (what round one of 80 8-of-20 inputs answered, the pool's bytes
    while the session held its store): 8,320 checks, over a megabyte, so
    the session's list is one `native/interp.hpp` `StorePool` keeps."""
    from bitcoinconsensus_tpu.core.flags import height_to_flags

    spends = [Spend(f"multisig/pool/{i}", SIZE_M, SIZE_N, range(SIZE_M), "sound") for i in range(80)]
    _block, records, _coins = build(spends, [20] * 4)
    flags = height_to_flags(HEIGHT, extended=True)
    ntxs, n_ins, amounts, spks = [], [], [], []
    for raw, outs in records:
        ntx = native_bridge.NativeTx(raw)
        ntx.set_spent_outputs(outs)
        for i, (amount, spk) in enumerate(outs):
            ntxs.append(ntx), n_ins.append(i), amounts.append(amount), spks.append(spk)

    def run():
        sess = native_bridge.NativeSession()
        ok, err, unk, rec_idx, bounds = sess.verify_inputs_idx(
            ntxs, n_ins, amounts, spks, [flags] * len(ntxs), n_threads=4)
        got = (ok.tolist(), err.tolist(), unk.tolist(), rec_idx.tolist(), bounds.tolist(),
               sess.uniq_count(), sess.spec_pairings(), sess.call_walks(len(ntxs)).tolist())
        held = native_bridge.store_pool_bytes()
        sess.release()
        return got, held

    return run


def test_a_released_sessions_store_is_reused_and_changes_nothing(round_one):
    """The index-mode session's check list is emptied and parked at
    release, and the next session takes it: same indices, same verdicts,
    same walks."""
    first, _ = round_one()
    parked = native_bridge.store_pool_bytes()
    assert first[5] == first[6] == 80 * SIZE_M * (SIZE_N - SIZE_M + 1)
    assert first[7] == [SIZE_M] * 80  # round one: every signature's first guess holds
    assert parked >= 1 << 20
    second, held = round_one()
    assert second == first
    assert held < parked  # the second session had taken a parked store
    assert native_bridge.store_pool_bytes() >= parked  # and gave it back


def test_sessions_on_many_threads_share_the_pool_and_answer_alike(round_one):
    """More threads than cores, each taking and parking stores while the
    others run (the native call releases the GIL): every answer equal to a
    lone session's, and nobody stuck on the pool's lock."""
    import os
    import sys
    import threading

    want, _ = round_one()
    n_threads, answers, errors = 2 * (os.cpu_count() or 4), [], []

    def worker():
        try:
            for _ in range(4):
                answers.append(round_one()[0] == want)
        except Exception as e:  # reported below, on the test's thread
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, daemon=True) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    assert answers == [True] * (4 * n_threads)
    assert native_bridge.store_pool_bytes() <= 256 << 20
