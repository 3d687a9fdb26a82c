"""The worst block a miner can make a validator check: bare m-of-20
CHECKMULTISIG behind P2WSH, at the sigop-cost limit.

`benchmarks/configs/worst-block.json` runs 4,000 such inputs on the chip
(80,000 curve checks, ten 8,192-lane dispatches a connect). Here the same
shape runs small on the CPU: a connect of 1-of-20 spends that takes several
chunks a round against the executable spec, the budget at exactly its
limit in both accountings, and the number of lanes an m-of-20 input costs
(one input, all signatures sound: the band's formula). What m > 1 does
through `connect_block`, twins and counters and all, is
`tests/test_multisig_block.py`, and at size on the chip the cell
`worst-block-multisig20.fanout`.
"""

import hashlib

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.block import MAX_BLOCK_SIGOPS_COST
from bitcoinconsensus_tpu.core.flags import height_to_flags
from bitcoinconsensus_tpu.core.script import OP_CHECKMULTISIG, push_data
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.core.sighash import SIGHASH_ALL, bip143_sighash
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import Coin, CoinsView, connect_block
from bitcoinconsensus_tpu.obs import get_registry
from bitcoinconsensus_tpu.utils.blockgen import (
    REGTEST_POW_LIMIT,
    FundedOutput,
    build_block,
    build_spend_tx,
)

from test_native_block import HEIGHT, to_native_view

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: the 16-lane rung
]

N_KEYS = 20
AMOUNT = 1_000_000


def _push_num(n: int) -> bytes:
    return bytes([0x50 + n]) if 1 <= n <= 16 else push_data(bytes([n]))


def multisig_script(m: int, pubs) -> bytes:
    """Bare `m <keys> n CHECKMULTISIG`; 20 is past OP_16, so a one-byte push."""
    return (_push_num(m) + b"".join(push_data(p) for p in pubs)
            + _push_num(len(pubs)) + bytes([OP_CHECKMULTISIG]))


class MultisigWallet:
    """A P2WSH m-of-20 output whose spend is signed by the keys at `signers`
    (positions in push order, ascending: the order CHECKMULTISIG wants the
    signatures in). Stands where `blockgen.Wallet` stands in `build_spend_tx`."""

    kind = "p2wsh_multisig20"

    def __init__(self, seed: str, signers, real_keys: bool = True):
        base = int.from_bytes(hashlib.sha256(seed.encode()).digest(), "big") % (H.N - N_KEYS)
        self.signers = list(signers)
        self.sks = [base + 1 + j for j in range(N_KEYS)]
        if real_keys:
            self.pubs = [H.pubkey_create(sk) for sk in self.sks]
        else:  # accounting never looks inside a key
            self.pubs = [b"\x02" + hashlib.sha256(b"%d" % sk).digest() for sk in self.sks]
        self.witness_script = multisig_script(len(self.signers), self.pubs)
        self.spk = b"\x00\x20" + hashlib.sha256(self.witness_script).digest()

    def sign_input(self, tx, n_in, amount, txdata=None, corrupt=False):
        sighash = bip143_sighash(self.witness_script, tx, n_in, SIGHASH_ALL, amount)
        sigs = [H.sign_ecdsa(self.sks[k], sighash) + bytes([SIGHASH_ALL])
                for k in self.signers]
        if corrupt:
            sigs[0] = sigs[0][:9] + bytes([sigs[0][9] ^ 1]) + sigs[0][10:]
        tx.vin[n_in].witness = [b""] + sigs + [self.witness_script]
        tx.invalidate_caches()


def fund(wallets, seed: str):
    coins, funded = CoinsView(), []
    for i, w in enumerate(wallets):
        op = OutPoint(hashlib.sha256(f"{seed}/op/{i}".encode()).digest(), i & 0xFFFF)
        coins.add(op, Coin(TxOut(AMOUNT, w.spk), height=1, coinbase=False))
        funded.append(FundedOutput(op, w, AMOUNT))
    return coins, funded


def _total(name: str, **labels) -> float:
    """A metric summed over its label sets, or over those that have `labels`."""
    snap = get_registry().snapshot().get(name, {"samples": []})
    return sum(s.get("value", s.get("sum", 0.0)) for s in snap["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


class _Rose:
    """Growth of registry metrics over a `with` block."""

    NAMES = (
        "consensus_dispatch_total", "consensus_dispatch_lanes_total",
        "consensus_fixpoint_reinterpreted_inputs_total",
        "consensus_multisig_spec_pairings_total",
        "consensus_exact_fallback_total", "consensus_fixpoint_rounds",
        "consensus_inflight_backpressure_total",
    )
    SIGHASHES = ("computed", "reused")  # consensus_sighash_total{result}

    def _read(self):
        out = {n: _total(n) for n in self.NAMES}
        out.update({"consensus_sighash_" + r: _total("consensus_sighash_total", result=r)
                    for r in self.SIGHASHES})
        return out

    def __enter__(self):
        self.before = self._read()
        return self

    def __exit__(self, *exc):
        self.rose = {n: v - self.before[n] for n, v in self._read().items()}

    def __getitem__(self, name):
        return self.rose["consensus_" + name]


class SpecCurve:
    """The executable spec's curve: `secp_host`, one check at a time."""

    def _host_check(self, chk) -> bool:
        assert chk.kind == "ecdsa"
        return H.verify_ecdsa(*chk.data)

    def verify_checks(self, checks):
        return np.array([self._host_check(c) for c in checks], dtype=bool)


def spec_connect(block, coins):
    """`_connect_block_impl` on the pure-Python interpreter and `secp_host`:
    no native core, no device, no batching beyond the wire driver's."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(native_bridge, "available", lambda: False)
        return connect_block(
            block, coins, HEIGHT, pow_limit=REGTEST_POW_LIMIT, verifier=SpecCurve(),
            sig_cache=SigCache(), script_cache=ScriptExecutionCache(),
        )


# -- a connect of several chunks a round, against the executable spec ------

POSITIONS = {"first-pushed": 0, "middle": 9, "last-pushed": 19}
N_INPUTS, VICTIM = 4, 2
LANES = N_INPUTS * N_KEYS  # 80: six dispatches of the 16-lane rung
DISPATCHES = -(-LANES // 15)


@pytest.fixture(scope="module")
def blocks():
    """Per signing position: the block, its corrupted twin, the coins and
    what the executable spec says of both (made once, read by both depths)."""
    made = {}

    def get(position: str):
        if position not in made:
            k = POSITIONS[position]
            wallets = [MultisigWallet(f"worst/{position}/{i}", [k]) for i in range(N_INPUTS)]
            coins, funded = fund(wallets, f"worst/{position}")
            groups = [funded[:2], funded[2:]]
            good = build_block([build_spend_tx(g) for g in groups], HEIGHT, fees=2000)
            bad = build_block(
                [build_spend_tx(groups[0]), build_spend_tx(groups[1], corrupt_input=VICTIM - 2)],
                HEIGHT, fees=2000,
            )
            made[position] = {
                "coins": coins, "good": good, "bad": bad,
                "spec_good": spec_connect(good, to_python_copy(coins)),
                "spec_bad": spec_connect(bad, to_python_copy(coins)),
            }
        return made[position]

    return get


def to_python_copy(coins: CoinsView) -> CoinsView:
    out = CoinsView()
    out._map.update(coins._map)
    return out


def same_result(got, want):
    assert (got.ok, got.reason, got.sigop_cost) == (want.ok, want.reason, want.sigop_cost)
    assert [(r.ok, r.error, r.script_error) for r in got.input_results] == \
        [(r.ok, r.error, r.script_error) for r in want.input_results]


@pytest.mark.parametrize("max_depth", [1, 4])
@pytest.mark.parametrize("position", list(POSITIONS))
def test_multichunk_connect_equals_the_spec(blocks, position, max_depth):
    b = blocks(position)
    assert b["spec_good"].ok and b["spec_good"].sigop_cost == LANES
    verifier = TpuSecpVerifier(min_batch=16, chunk=16)
    verifier._inflight.max_depth = max_depth
    connect = dict(pow_limit=REGTEST_POW_LIMIT, verifier=verifier)

    view = to_native_view(b["coins"])
    with _Rose() as rose:
        res = connect_block(b["good"].serialize(), view, HEIGHT, sig_cache=SigCache(),
                            script_cache=ScriptExecutionCache(), **connect)
    same_result(res, b["spec_good"])
    # Every pairing the walk can reach is pre-recorded in round one, so the
    # round's chunks carry all 20 an input and a later round launches nothing.
    wrong_guess = position != "last-pushed"  # the walk tries the last-pushed key first
    assert rose["dispatch_total"] == DISPATCHES
    assert rose["dispatch_lanes_total"] == LANES
    assert rose["multisig_spec_pairings_total"] == LANES
    assert rose["fixpoint_reinterpreted_inputs_total"] == (N_INPUTS if wrong_guess else 0)
    assert rose["fixpoint_rounds"] == (2 if wrong_guess else 1)
    assert rose["exact_fallback_total"] == 0
    # An input's digest is hashed once a round it is interpreted in and read
    # again by every pairing of the walk: the one optimistic pairing in round
    # one, and in round two every key down to the one that signed.
    walk = N_KEYS - POSITIONS[position]
    assert rose["sighash_computed"] == N_INPUTS * (2 if wrong_guess else 1)
    assert rose["sighash_reused"] == N_INPUTS * (1 + (walk if wrong_guess else 0))
    # The queue lets `max_depth` tickets out; every further chunk of the round
    # waits for the oldest, in the `backpressure` phase and not in `dispatch`.
    waits = DISPATCHES - max_depth
    assert rose["inflight_backpressure_total"] == waits
    phases = verifier.phases.report()
    assert phases["backpressure"]["calls"] == waits
    assert phases["dispatch"]["calls"] == DISPATCHES
    assert verifier._inflight.depth == 0

    # The corrupted twin: all 20 pairings of the victim fail, its script
    # ends false, the block is rejected for it alone and the view stays.
    view = to_native_view(b["coins"])
    with _Rose() as rose:
        res = connect_block(b["bad"].serialize(), view, HEIGHT, sig_cache=SigCache(),
                            script_cache=ScriptExecutionCache(), **connect)
    same_result(res, b["spec_bad"])
    assert not res.ok and res.reason == "block-validation-failed"
    assert res.script_failures == [VICTIM]
    assert res.input_results[VICTIM].script_error == ScriptError.EVAL_FALSE
    assert len(view) == N_INPUTS
    assert rose["dispatch_total"] == DISPATCHES and rose["exact_fallback_total"] == 0
    assert rose["fixpoint_reinterpreted_inputs_total"] == (N_INPUTS if wrong_guess else 1)


# -- the budget at exactly its limit ------------------------------------------

def _budget_block(n_inputs: int):
    """`n_inputs` unsigned 1-of-20 spends, 25 a transaction: accounting
    rejects before any script runs, so no signature and no key is real."""
    wallet = MultisigWallet("worst/budget", [0], real_keys=False)
    coins, funded = fund([wallet] * n_inputs, "worst/budget")
    witness = [b"", b"\x30\x06\x02\x01\x01\x02\x01\x01\x01", wallet.witness_script]
    txs = []
    for at in range(0, n_inputs, 25):
        group = funded[at : at + 25]
        tx = Tx(version=2, vin=[TxIn(f.outpoint) for f in group],
                vout=[TxOut(AMOUNT * len(group) - 1000, b"\x00\x14" + b"\x11" * 20)], locktime=0)
        for txin in tx.vin:
            txin.witness = list(witness)
        txs.append(tx)
    return build_block(txs, HEIGHT, fees=1000 * len(txs)), coins


@pytest.mark.parametrize("n_inputs,reason", [(4000, None), (4001, "bad-blk-sigops")])
def test_sigop_budget_at_its_limit(n_inputs, reason):
    """4,000 inputs cost exactly MAX_BLOCK_SIGOPS_COST and pass; one more
    is `bad-blk-sigops`, in native/block.hpp and in models/validate.py."""
    assert MAX_BLOCK_SIGOPS_COST == 4000 * N_KEYS
    block, coins = _budget_block(n_inputs)
    weight = 3 * len(block.serialize(include_witness=False)) + len(block.serialize())
    assert weight < 4_000_000
    for view in (to_native_view(coins), coins):  # the native accounting, then Python's
        before = len(view)
        res = connect_block(block, view, HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                            check_scripts=False)
        assert (res.ok, res.reason) == (reason is None, reason)
        if reason is None:
            assert res.sigop_cost == MAX_BLOCK_SIGOPS_COST
            assert len(view) == before - n_inputs + sum(len(t.vout) for t in block.vtx)
        else:
            assert len(view) == before


# -- what an m-of-20 costs in lanes ---------------------------------------------

@pytest.mark.parametrize("m", [1, 2, 8, 20])
def test_lanes_an_input_for_m_of_20(m):
    """Each signature is pre-recorded against the 21 - m keys its cursor can
    reach: m * (21 - m) lanes an input (Core's own walk verifies at most 20)."""
    wallet = MultisigWallet(f"worst/m{m}", list(range(m)))
    _coins, funded = fund([wallet], f"worst/m{m}")
    tx = build_spend_tx(funded)
    item = BatchItem(tx.serialize(), 0, height_to_flags(HEIGHT, extended=True),
                     spent_outputs=[(AMOUNT, wallet.spk)])
    verifier = TpuSecpVerifier(min_batch=16, chunk=16)
    with _Rose() as rose:
        (res,) = verify_batch([item], verifier, SigCache(), ScriptExecutionCache())
    assert res.ok
    assert rose["dispatch_lanes_total"] == m * (21 - m)
    assert rose["multisig_spec_pairings_total"] == m * (21 - m)
    assert rose["exact_fallback_total"] == 0
