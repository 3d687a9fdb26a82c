"""The block front end from the wire bytes: parse and accounting.

`NativeBlock(raw)` hashes every transaction's ids from the span of the
block's own bytes it was read from, and `NativeBlock.accounting` runs a pass
that decides and a pass that hashes and fills (records, spent digests, the
hash precompute, the script-cache keys). What they return must be what the
Python spec computes (`core/block.py`, `models/validate._connect_block_impl`,
`models/sigcache.py`). The blocks here are the benchmark's own mixes at a
quarter or a fifth of their size, the block `fuzz/run.sh` seeds its corpus
with, and padded blocks whose few signed inputs fit a CPU dispatch.
"""

import hashlib

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import native_bridge as NB
from bitcoinconsensus_tpu.core.block import Block
from bitcoinconsensus_tpu.core.flags import height_to_flags
from bitcoinconsensus_tpu.core.tx import COIN, MAX_MONEY, OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.models import validate
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import Coin, CoinsView, connect_block
from bitcoinconsensus_tpu.utils.blockgen import (
    REGTEST_POW_LIMIT,
    build_block,
    build_spend_tx,
    make_funded_view,
)

from test_native_block import HEIGHT, _result_tuple, to_native_view
from test_vectors_json import load_json

pytestmark = pytest.mark.skipif(
    not NB.available(), reason="native core unavailable"
)

SALT = bytes(range(32))


def sha256d(b: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(b).digest()).digest()


# --- the blocks --------------------------------------------------------------


def _python_view(coins) -> CoinsView:
    view = CoinsView()
    for txid, n, value, height, coinbase, spk in coins:
        view.add(OutPoint(txid, n), Coin(TxOut(value, spk), height=height, coinbase=bool(coinbase)))
    return view


def _tip_mix():
    from benchmarks.generators import block
    from benchmarks.run import load_spec, merge

    spec = load_spec("tip-block.cold")
    config = merge(spec["config"], {"block": {
        "inputs": 1500, "txs": 600,
        "inputs_per_tx": {"1": 330, "2": 120, "3": 60, "4": 30, "6": 30, "10": 20, "25": 10},
    }})
    d = block.build(config, spec["traffic"], 7, 0)
    return d["block"], d["coins"], int(d["height"])


def _legacy_mix():
    from benchmarks.generators import chain
    from benchmarks.run import load_spec, merge

    spec = load_spec("ibd-stream.cold")
    config = merge(spec["config"], {"chain": {
        "blocks": 2, "txs": 312, "inputs": 1225, "in_stream_spends": 60, "corrupt_block": 1,
        "inputs_per_tx": {"1": 135, "2": 68, "3": 36, "4": 24, "6": 20, "10": 14, "20": 7, "50": 7},
    }})
    d = chain.build(config, spec["traffic"], 7, 0)
    return d["blocks"][0], d["coins"], int(d["start_height"])


def _worst_mix():
    from benchmarks.generators import worstblock
    from benchmarks.run import load_spec, merge

    spec = load_spec("worst-block.sigops")
    config = merge(spec["config"], {"block": {"inputs": 250, "txs": 10, "sigop_cost": 5000}})
    d = worstblock.build(config, spec["traffic"], 7, 0)
    return d["block"], d["coins"], int(d["height"])


def _fuzz_seed():
    """The block `fuzz/run.sh` writes into its seed corpus."""
    coins, funded = make_funded_view(4, kinds=("p2wpkh", "p2tr", "p2wsh_multisig"), seed="fuzz")
    block = build_block([build_spend_tx(funded, fee=700)], 710_000, fees=700)
    rows = [(op[0], op[1], c.out.value, c.height, c.coinbase, c.out.script_pubkey)
            for op, c in coins._map.items()]
    return block.serialize(), rows, 710_000


_SHAPES = {"tip": _tip_mix, "legacy": _legacy_mix, "worst": _worst_mix, "fuzz_seed": _fuzz_seed}
_built = {}


def shape(name):
    """(raw block, coin rows, height, what the Python spec says of it)."""
    if name not in _built:
        raw, coins, height = _SHAPES[name]()
        _built[name] = (raw, coins, height, _spec(raw, coins, height))
    return _built[name]


def _spec(raw: bytes, coins, height: int) -> dict:
    """Every front-end output, from the Python layer alone."""
    block = Block.deserialize(raw)
    flags = height_to_flags(height, extended=True)
    view = _python_view(coins)
    cache = ScriptExecutionCache()
    cache._salt = SALT
    created = {}
    tx_index, n_in, amounts, spks, digests, keys = [], [], [], [], [], []
    for t, tx in enumerate(block.vtx):
        if t == 0:
            digests.append(b"\x00" * 32)
        else:
            outs = []
            for txin in tx.vin:
                op = (txin.prevout.hash, txin.prevout.n)
                out = created[op] if op in created else view.get(txin.prevout).out
                outs.append((out.value, out.script_pubkey))
            digest = ScriptExecutionCache.spent_digest(outs)
            digests.append(digest)
            for i, (value, spk) in enumerate(outs):
                tx_index.append(t)
                n_in.append(i)
                amounts.append(value)
                spks.append(spk)
                keys.append(cache._key(cache._parts(tx.wtxid, i, flags, digest)))
        for n, out in enumerate(tx.vout):
            created[(tx.txid, n)] = out
    res = validate._connect_block_impl(
        block, view, height, flags, None, True, False, None, REGTEST_POW_LIMIT, None, None)
    assert res.ok, res.reason
    return {
        "txids": [tx.txid for tx in block.vtx],
        "wtxids": [tx.wtxid for tx in block.vtx],
        "nowit_size": [len(tx.serialize(include_witness=False)) for tx in block.vtx],
        "ser_size": [len(tx.serialize()) for tx in block.vtx],
        "fees": res.fees, "sigop_cost": res.sigop_cost,
        "tx_index": tx_index, "n_in": n_in, "amounts": amounts,
        "spk_offs": np.cumsum([0] + [len(s) for s in spks]).tolist(),
        "spk_blob": b"".join(spks),
        "spent_digests": b"".join(digests), "script_keys": b"".join(keys),
    }


def _native(raw: bytes, coins, height: int) -> dict:
    """The same outputs from the native front end."""
    flags = height_to_flags(height, extended=True)
    view = NB.NativeCoinsView()
    view.add_coins_batch(coins)
    nblk = NB.NativeBlock(raw)
    reason, fees, sigops, tx_index, n_in, amounts, spk_offs, spk_blob = nblk.accounting(
        view, height, flags, SALT)
    assert reason is None
    return {
        "txids": [nblk.txid(i) for i in range(nblk.n_tx)],
        "wtxids": [nblk.wtxid(i) for i in range(nblk.n_tx)],
        "nowit_size": nblk.nowit_sizes().tolist(),
        "ser_size": [nblk.tx(i).ser_size for i in range(nblk.n_tx)],
        "fees": fees, "sigop_cost": sigops,
        "tx_index": tx_index.tolist(), "n_in": n_in.tolist(), "amounts": amounts.tolist(),
        "spk_offs": spk_offs.tolist(),
        "spk_blob": spk_blob[: int(spk_offs[-1])].tobytes(),
        "spent_digests": nblk.spent_digests().tobytes(),
        "script_keys": nblk.script_keys().tobytes(),
    }


# --- the Python spec -------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(_SHAPES))
def test_front_end_is_the_python_spec(name):
    """txids, wtxids, both sizes, fees, sigop cost, the five accounting
    arrays, spent digests and script-cache keys equal the Python layer's."""
    raw, coins, height, spec = shape(name)
    got = _native(raw, coins, height)
    for field, want in spec.items():
        assert got[field] == want, field


def test_accounting_without_a_salt_makes_no_key():
    raw, coins, height, _ = shape("fuzz_seed")
    view = NB.NativeCoinsView()
    view.add_coins_batch(coins)
    nblk = NB.NativeBlock(raw)
    assert nblk.accounting(view, height, height_to_flags(height, extended=True))[0] is None
    out = np.zeros((nblk.n_inputs, 32), dtype=np.uint8)
    assert NB.lib().nat_block_script_keys(nblk._ptr, NB._u8p(out)) == 0
    with pytest.raises(ValueError):
        nblk.script_keys()


# --- padded blocks: a block's worth of bytes, a CPU dispatch of signature checks ---

_PAD_SPK = b"\x51"  # OP_TRUE: spent with an empty scriptSig, no curve check


def _padding(n: int, seed: str, coins: CoinsView, out_bytes: int = 1000):
    """`n` transactions that each spend an anyone-can-spend coin into one
    output of `out_bytes` (no signature anywhere), their coins added to
    `coins`; fee 1,000 sat each."""
    txs = []
    for i in range(n):
        op = OutPoint(hashlib.sha256(f"{seed}/pad/{i}".encode()).digest(), i)
        coins.add(op, Coin(TxOut(50_000, _PAD_SPK), height=1, coinbase=False))
        # OP_RESERVED and OP_1..OP_15 after an OP_RETURN: no sigop among them
        digest = hashlib.sha256(f"{seed}/body/{i}".encode()).digest()
        body = bytes((b & 0x0F) | 0x50 for b in digest) * (out_bytes // 32)
        txs.append(Tx(2, [TxIn(op)], [TxOut(49_000, b"\x6a" + body)], 0))
    return txs


@pytest.mark.usefixtures("warm_kernel")
@pytest.mark.parametrize("corrupt", (None, 1), ids=("valid", "one_flipped"))
def test_connect_block_verdicts(corrupt):
    """A 200 kB block (six signed inputs among 190 unsigned transactions),
    as it is and with one signature flipped: `connect_block` on the native
    view answers as the Python spec does."""
    coins, funded = make_funded_view(
        6, kinds=("p2wpkh", "p2tr", "p2wsh_multisig"), seed="front")
    signed = [build_spend_tx(funded[0:2], fee=800),
              build_spend_tx(funded[2:4], fee=800, corrupt_input=corrupt),
              build_spend_tx(funded[4:6], fee=800)]
    pad = _padding(190, "front", coins)
    txs = pad[:60] + signed[:1] + pad[60:120] + signed[1:2] + pad[120:] + signed[2:]
    block = build_block(txs, HEIGHT, fees=2400 + 1000 * len(pad))
    raw = block.serialize()
    nview = to_native_view(coins)
    kw = dict(pow_limit=REGTEST_POW_LIMIT)
    res_py = connect_block(block, coins, HEIGHT, sig_cache=SigCache(),
                           script_cache=ScriptExecutionCache(), **kw)
    res_nat = connect_block(raw, nview, HEIGHT, sig_cache=SigCache(),
                            script_cache=ScriptExecutionCache(), **kw)
    assert _result_tuple(res_nat) == _result_tuple(res_py)
    assert res_py.ok == (corrupt is None)
    if corrupt is not None:
        assert res_nat.script_failures == [120 + 2 + 1]
    assert len(nview) == len(coins)


# --- the order defects are reported in -----------------------------------------


def _defect_txs(coins: CoinsView):
    """Transactions with one defect each that only accounting can see."""
    def coin(tag, value, spk=_PAD_SPK, height=1, coinbase=False):
        op = OutPoint(hashlib.sha256(f"defect/{tag}".encode()).digest(), 0)
        coins.add(op, Coin(TxOut(value, spk), height=height, coinbase=coinbase))
        return op

    def spend(op, value_out):
        return Tx(2, [TxIn(op)], [TxOut(value_out, _PAD_SPK)], 0)

    # 4,001 bare CHECKMULTISIGs in a P2WSH witness script: 80,020 sigops,
    # none of them in check_block's legacy count.
    script = b"\xae" * 4001
    sigops = Tx(2, [TxIn(coin("sigops", 50_000, b"\x00\x20" + hashlib.sha256(script).digest()))],
                [TxOut(49_000, _PAD_SPK)], 0)
    sigops.vin[0].witness = [script]
    missing = spend(OutPoint(hashlib.sha256(b"defect/nowhere").digest(), 0), 1_000)
    premature = spend(coin("young", 50_000, height=HEIGHT - 50, coinbase=True), 49_000)
    rich = [spend(coin(f"rich/{k}", 11_000_000 * COIN), 1_000) for k in (0, 1)]
    assert 2 * (11_000_000 * COIN - 1_000) > MAX_MONEY
    return {"sigops": [sigops], "missing": [missing], "premature": [premature], "fee": rich}


_DEFECT_ORDERS = [
    (("sigops", "missing"), "bad-blk-sigops"),
    (("missing", "sigops"), "bad-txns-inputs-missingorspent"),
    (("premature", "fee"), "bad-txns-premature-spend-of-coinbase"),
    (("fee", "premature"), "bad-txns-fee-outofrange"),
]


@pytest.mark.parametrize("order,reason", _DEFECT_ORDERS, ids=[">".join(o) for o, _ in _DEFECT_ORDERS])
def test_two_defects_report_the_first_in_block_order(order, reason):
    """Two defects in different transactions of one block: the reason is
    the Python spec's, the earlier transaction's."""
    coins = CoinsView()
    defects = _defect_txs(coins)
    pad = _padding(150, "defect", coins)
    txs = pad[:50] + defects[order[0]] + pad[50:100] + defects[order[1]] + pad[100:]
    block = build_block(txs, HEIGHT, fees=0)
    raw = block.serialize()
    nview = to_native_view(coins)
    kw = dict(pow_limit=REGTEST_POW_LIMIT, check_scripts=False)
    res_py = connect_block(block, coins, HEIGHT, **kw)
    res_nat = connect_block(raw, nview, HEIGHT, **kw)
    assert (res_py.ok, res_py.reason) == (False, reason)
    assert _result_tuple(res_nat) == _result_tuple(res_py)
    assert len(nview) == len(coins)  # a refused block leaves the view alone


# --- the parse reads a tx's own bytes --------------------------------------------


def _native_serialize(ntx, witness: bool) -> bytes:
    import ctypes

    L = NB.lib()
    L.nat_tx_serialize_size.restype = ctypes.c_int64
    L.nat_tx_serialize_size.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    L.nat_tx_serialize.argtypes = [ctypes.c_void_p, ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8)]
    n = int(L.nat_tx_serialize_size(ntx._ptr, int(witness)))
    out = np.zeros(n, np.uint8)
    L.nat_tx_serialize(ntx._ptr, int(witness), NB._u8p(out))
    return out.tobytes()


def _spans_are_serializations(raws):
    """For every transaction of `raws` that parses: the bytes consumed are
    `serialize(true)`, their double hash is the wtxid, and the three pieces
    a block's parse hashes give `sha256d(serialize(false))` (checked by
    wrapping the transaction in a one-transaction block); trailing bytes
    after a lone transaction are left unread. Returns how many parsed and
    how many of those carry a witness."""
    n = with_witness = 0
    for raw in raws:
        try:
            ntx = NB.NativeTx(raw)
        except ValueError:
            continue
        n += 1
        assert NB.NativeTx(raw + b"\xfd\x01\x00").ser_size == ntx.ser_size
        full, base = _native_serialize(ntx, True), _native_serialize(ntx, False)
        assert ntx.ser_size == len(full) and raw[: ntx.ser_size] == full
        assert ntx.wtxid == sha256d(full)
        nblk = NB.NativeBlock(b"\x00" * 80 + b"\x01" + full)
        assert nblk.wtxid(0) == sha256d(full)
        assert nblk.txid(0) == sha256d(base)
        assert nblk.nowit_sizes().tolist() == [len(base)]
        assert nblk.tx(0).ser_size == len(full)
        with_witness += full != base
    return n, with_witness


def test_a_parsed_tx_is_the_bytes_it_was_read_from():
    """Over every transaction of the blocks above, and one with no input
    and no output (`00 00`: the count of inputs, then the byte that is the
    flag to the reader and the count of outputs to the writer)."""
    raws = [bytes.fromhex("01000000" "0000" "00000000")]
    for name in sorted(_SHAPES):
        raws.extend(tx.serialize() for tx in Block.deserialize(shape(name)[0]).vtx)
    n, with_witness = _spans_are_serializations(raws)
    assert n == len(raws) and with_witness > 500


@pytest.mark.parametrize("name", ["tx_valid.json", "tx_invalid.json"])
def test_a_reference_vector_tx_is_the_bytes_it_was_read_from(name):
    """The same over the reference checkout's transaction vectors."""
    raws = [bytes.fromhex(test[1]) for test in load_json(name) if isinstance(test[0], list)]
    n, with_witness = _spans_are_serializations(raws)
    assert n > 50 and with_witness > 10


@pytest.mark.parametrize("count", ["fd0100", "fe01000000", "ff0100000000000000"])
def test_a_non_canonical_count_does_not_parse(count):
    """One input, its count written the long way: the reader refuses it, so
    no span ever differs from its serialization."""
    tail = "00" * 32 + "00000000" + "00" + "ffffffff" + "01" + "0000000000000000" + "00" + "00000000"
    good = bytes.fromhex("01000000" + "01" + tail)
    assert NB.NativeTx(good).ser_size == len(good)
    bad = bytes.fromhex("01000000" + count + tail)
    with pytest.raises(ValueError):
        NB.NativeTx(bad)
    with pytest.raises(ValueError):
        NB.NativeBlock(b"\x00" * 80 + b"\x01" + bad)


# --- the hash every stage ends in ------------------------------------------------


def test_sha256_padding_at_every_length():
    """The final block is padded in one write: every residue of 64."""
    for n in list(range(0, 130)) + [191, 192, 255, 256, 1000]:
        data = bytes((7 * i + n) % 256 for i in range(n))
        arr = np.frombuffer(data, dtype=np.uint8) if n else np.zeros(1, np.uint8)
        out = np.zeros(32, dtype=np.uint8)
        NB.lib().nat_sha256(NB._u8p(arr), n, NB._u8p(out))
        assert out.tobytes() == hashlib.sha256(data).digest(), n
