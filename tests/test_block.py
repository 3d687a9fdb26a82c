"""Block layer: codec, merkle (CVE-2012-2459), PoW, CheckBlock rules,
witness commitment, and the ConnectBlock-shaped replay driver.

Reference spec: `primitives/block.h`, `consensus/merkle.cpp:45-84`,
`pow.cpp:74-90`, `validation.cpp:3402-3474` (CheckBlock),
`validation.cpp:3385-3428` (witness commitment), `validation.cpp:1946-2230`
(ConnectBlock phases) — behavior matched, structure TPU-native
(`models/validate.py` batches every input's signature algebra).
"""

import hashlib

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.core.block import (
    Block,
    bits_to_target,
    block_merkle_root,
    check_block,
    check_proof_of_work,
    check_witness_commitment,
    merkle_root,
    witness_commitment_index,
)
from bitcoinconsensus_tpu.core.tx import COIN, OutPoint, Tx, TxIn, TxOut
from bitcoinconsensus_tpu.models.validate import (
    COINBASE_MATURITY,
    Coin,
    connect_block,
    get_block_subsidy,
    get_transaction_sigop_cost,
)
from bitcoinconsensus_tpu.utils.blockgen import (
    REGTEST_BITS,
    REGTEST_POW_LIMIT,
    Wallet,
    build_block,
    build_spend_tx,
    make_funded_view,
)
from bitcoinconsensus_tpu.utils.hashes import sha256d

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls

HEIGHT = 500_000  # post-segwit mainnet schedule (P2SH..WITNESS active)
T_HEIGHT = 710_000  # post-taproot


def _connect(block, coins, height=HEIGHT, **kw):
    kw.setdefault("pow_limit", REGTEST_POW_LIMIT)
    return connect_block(block, coins, height, **kw)


# -- merkle -----------------------------------------------------------------


def test_merkle_empty_and_single():
    assert merkle_root([]) == (b"\x00" * 32, False)
    h = hashlib.sha256(b"x").digest()
    assert merkle_root([h]) == (h, False)


def test_merkle_pair_and_odd_duplication():
    a, b, c = (hashlib.sha256(bytes([i])).digest() for i in range(3))
    root2, mut2 = merkle_root([a, b])
    assert root2 == sha256d(a + b) and not mut2
    # Odd count: last leaf duplicated (the CVE-2012-2459 quirk).
    root3, mut3 = merkle_root([a, b, c])
    assert root3 == sha256d(sha256d(a + b) + sha256d(c + c)) and not mut3


def test_merkle_mutation_detected():
    a, b = (hashlib.sha256(bytes([i])).digest() for i in range(2))
    # Adjacent identical leaves at an even offset -> mutation flag.
    _, mutated = merkle_root([a, a, b])
    assert mutated
    # The CVE-2012-2459 collision (merkle.cpp:17-28 comment): [1..6] and
    # [1..6,5,6] produce the SAME root; the flag is the only defense.
    leaves = [hashlib.sha256(bytes([i])).digest() for i in range(6)]
    r1, m1 = merkle_root(leaves)
    r2, m2 = merkle_root(leaves + leaves[4:6])
    assert r1 == r2 and not m1 and m2


# -- PoW --------------------------------------------------------------------


def test_bits_to_target_compact():
    # 0x1d00ffff: mainnet genesis difficulty.
    target, neg, over = bits_to_target(0x1D00FFFF)
    assert target == 0xFFFF << (8 * (0x1D - 3)) and not neg and not over
    # Negative bit set.
    assert bits_to_target(0x1D80FFFF)[1]
    # Overflow: size too large.
    assert bits_to_target(0x23000101)[2]
    # Small sizes shift the word down (SetCompact nSize <= 3 branch).
    assert bits_to_target(0x01100000)[0] == 0x100000 >> 16


def test_check_proof_of_work():
    # A hash equal to the target passes; one above fails.
    target, _, _ = bits_to_target(REGTEST_BITS)
    good = target.to_bytes(32, "little")
    assert check_proof_of_work(good, REGTEST_BITS, REGTEST_POW_LIMIT)
    bad = (target + 1).to_bytes(32, "little")
    assert not check_proof_of_work(bad, REGTEST_BITS, REGTEST_POW_LIMIT)
    # bits exceeding the pow limit are rejected outright.
    assert not check_proof_of_work(good, REGTEST_BITS, target - 1)


# -- block codec ------------------------------------------------------------


def test_block_roundtrip_and_hash():
    coins, funded = make_funded_view(4)
    txs = [build_spend_tx(funded[:2]), build_spend_tx(funded[2:])]
    block = build_block(txs, HEIGHT, fees=2000)
    raw = block.serialize()
    back = Block.deserialize(raw)
    assert back.serialize() == raw
    assert back.hash == block.hash
    assert [t.txid for t in back.vtx] == [t.txid for t in block.vtx]
    # Witness survives the round trip.
    assert back.vtx[1].has_witness()


def test_block_trailing_data_rejected():
    coins, funded = make_funded_view(1)
    block = build_block([build_spend_tx(funded)], HEIGHT, fees=1000)
    from bitcoinconsensus_tpu.core.serialize import SerializationError

    with pytest.raises(SerializationError):
        Block.deserialize(block.serialize() + b"\x00")


# -- CheckBlock rules -------------------------------------------------------


def test_check_block_valid():
    coins, funded = make_funded_view(4)
    block = build_block([build_spend_tx(funded)], HEIGHT, fees=1000)
    ok, reason = check_block(block, pow_limit=REGTEST_POW_LIMIT)
    assert ok, reason
    ok, reason = check_witness_commitment(block)
    assert ok, reason


def test_check_block_bad_merkle():
    coins, funded = make_funded_view(1)
    block = build_block([build_spend_tx(funded)], HEIGHT, fees=1000)
    block.header.merkle_root = b"\x11" * 32
    assert check_block(block, check_pow=False) == (False, "bad-txnmrklroot")


def test_check_block_duplicate_tx_mutation():
    # 6 txs -> appending the last two replays CVE-2012-2459: identical
    # level-2 hashes at an even offset, same root, mutation flagged.
    coins, funded = make_funded_view(5)
    txs = [build_spend_tx([f]) for f in funded]
    block = build_block(txs, HEIGHT, fees=5000)
    mutated = Block(block.header, block.vtx + block.vtx[-2:])
    root, flag = block_merkle_root(mutated)
    assert root == block.header.merkle_root and flag
    assert check_block(mutated, check_pow=False) == (False, "bad-txns-duplicate")


def test_check_block_coinbase_rules():
    coins, funded = make_funded_view(1)
    block = build_block([build_spend_tx(funded)], HEIGHT, fees=1000)
    # Remove the coinbase: first tx not coinbase.
    no_cb = Block(block.header, block.vtx[1:])
    assert check_block(no_cb, check_pow=False, check_merkle=False)[1] == "bad-cb-missing"
    # Two coinbases.
    two_cb = Block(block.header, [block.vtx[0], block.vtx[0]] + block.vtx[1:])
    assert check_block(two_cb, check_pow=False, check_merkle=False)[1] in (
        "bad-cb-multiple",
        "bad-txns-duplicate",
    )


def test_check_block_high_hash():
    coins, funded = make_funded_view(1)
    block = build_block([build_spend_tx(funded)], HEIGHT, fees=1000)
    # Mainnet limit is astronomically below the regtest-mined header.
    ok, reason = check_block(block)
    assert (ok, reason) == (False, "high-hash")


def test_witness_commitment_detection_and_mismatch():
    coins, funded = make_funded_view(2, kinds=("p2wpkh",))
    block = build_block([build_spend_tx(funded)], HEIGHT, fees=1000)
    idx = witness_commitment_index(block)
    assert idx == 1
    # Corrupt the committed hash.
    spk = block.vtx[0].vout[idx].script_pubkey
    block.vtx[0].vout[idx] = TxOut(0, spk[:6] + b"\xff" * 32)
    ok, reason = check_witness_commitment(block)
    assert (ok, reason) == (False, "bad-witness-merkle-match")


def test_witness_without_commitment_rejected():
    coins, funded = make_funded_view(1, kinds=("p2wpkh",))
    block = build_block(
        [build_spend_tx(funded)], HEIGHT, fees=1000, witness_commitment=False
    )
    assert check_witness_commitment(block) == (False, "unexpected-witness")


# -- subsidy / sigops -------------------------------------------------------


def test_block_subsidy_halvings():
    assert get_block_subsidy(0) == 50 * COIN
    assert get_block_subsidy(209_999) == 50 * COIN
    assert get_block_subsidy(210_000) == 25 * COIN
    assert get_block_subsidy(420_000) == 50 * COIN // 4
    assert get_block_subsidy(64 * 210_000) == 0


def test_transaction_sigop_cost_families():
    coins, funded = make_funded_view(4)  # p2pkh, p2wpkh, p2wsh, p2tr
    tx = build_spend_tx(funded)
    spent = [TxOut(f.amount, f.wallet.spk) for f in funded]
    from bitcoinconsensus_tpu.core.flags import VERIFY_P2SH, VERIFY_WITNESS

    cost = get_transaction_sigop_cost(tx, spent, VERIFY_P2SH | VERIFY_WITNESS)
    # p2pkh scriptSig pushes only (0) + outputs (0); legacy counts the
    # p2pkh spk only when it is an *output* — here outputs are OP_TRUE.
    # Witness: p2wpkh=1, p2wsh 2-of-3 multisig witness script=20 (inaccurate
    # MAX_PUBKEYS)... accurate=True in witness counting -> 3? No: accurate
    # counts OP_3 preceding CHECKMULTISIG -> 3. p2tr counts 0.
    assert cost == 1 + 3


# -- connect_block ----------------------------------------------------------


def test_connect_block_applies_and_updates_view():
    coins, funded = make_funded_view(8)
    n0 = len(coins)
    txs = [build_spend_tx(funded[:4], fee=2000), build_spend_tx(funded[4:], fee=2000)]
    block = build_block(txs, T_HEIGHT, fees=4000)
    res = _connect(block, coins, T_HEIGHT)
    assert res.ok, res.reason
    assert res.fees == 4000
    assert res.input_results is not None and all(r.ok for r in res.input_results)
    # 8 inputs spent; coinbase(2 outs) + 2 spend outputs added.
    assert len(coins) == n0 - 8 + 2 + 2


def test_connect_block_bad_signature_fails_block():
    coins, funded = make_funded_view(4)
    txs = [build_spend_tx(funded, fee=1000, corrupt_input=2)]
    block = build_block(txs, T_HEIGHT, fees=1000)
    n0 = len(coins)
    res = _connect(block, coins, T_HEIGHT)
    assert not res.ok and res.reason == "block-validation-failed"
    assert res.script_failures == [2]
    assert len(coins) == n0  # view untouched on failure


def test_connect_block_missing_input():
    coins, funded = make_funded_view(2)
    tx = build_spend_tx(funded)
    block = build_block([tx], T_HEIGHT, fees=2000)
    coins.spend(funded[0].outpoint)  # make the first input vanish
    res = _connect(block, coins, T_HEIGHT)
    assert (res.ok, res.reason) == (False, "bad-txns-inputs-missingorspent")


def test_connect_block_double_spend_within_block():
    coins, funded = make_funded_view(1)
    t1 = build_spend_tx(funded, fee=500)
    t2 = build_spend_tx(funded, fee=600)  # spends the same outpoint
    block = build_block([t1, t2], T_HEIGHT, fees=1100)
    res = _connect(block, coins, T_HEIGHT)
    assert (res.ok, res.reason) == (False, "bad-txns-inputs-missingorspent")


def test_connect_block_premature_coinbase_spend():
    coins, funded = make_funded_view(1, height=T_HEIGHT - 10)
    # Mark the funding coin as a coinbase output: too young to spend.
    op = funded[0].outpoint
    coin = coins.get(op)
    coins.add(op, Coin(coin.out, coin.height, coinbase=True))
    block = build_block([build_spend_tx(funded)], T_HEIGHT, fees=1000)
    res = _connect(block, coins, T_HEIGHT)
    assert (res.ok, res.reason) == (False, "bad-txns-premature-spend-of-coinbase")
    # Matured coinbase spends fine.
    coins2, funded2 = make_funded_view(1, height=T_HEIGHT - COINBASE_MATURITY)
    op2 = funded2[0].outpoint
    c2 = coins2.get(op2)
    coins2.add(op2, Coin(c2.out, c2.height, coinbase=True))
    block2 = build_block([build_spend_tx(funded2)], T_HEIGHT, fees=1000)
    assert _connect(block2, coins2, T_HEIGHT).ok


def test_connect_block_bip30_duplicate_txid_rejected():
    """A tx whose txid already has unspent outputs in the view must be
    rejected (Core's BIP30 HaveCoin scan) instead of overwriting the coin."""
    coins, funded = make_funded_view(1)
    tx = build_spend_tx(funded, fee=1000)
    # Plant the tx's outputs as already-unspent coins (as if an identical
    # txid had been connected before).
    coins.add_tx(tx, HEIGHT - 50)
    block = build_block([tx], T_HEIGHT, fees=1000)
    res = _connect(block, coins, T_HEIGHT)
    assert (res.ok, res.reason) == (False, "bad-txns-BIP30")


def test_connect_block_value_conservation():
    coins, funded = make_funded_view(1)
    tx = build_spend_tx(funded, fee=1000)
    tx.vout[0] = TxOut(tx.vout[0].value + 5000, tx.vout[0].script_pubkey)
    # Signature is now wrong too, but value check fires first.
    block = build_block([tx], T_HEIGHT, fees=1000)
    res = _connect(block, coins, T_HEIGHT)
    assert (res.ok, res.reason) == (False, "bad-txns-in-belowout")


def test_connect_block_greedy_coinbase():
    coins, funded = make_funded_view(1)
    block = build_block([build_spend_tx(funded, fee=1000)], T_HEIGHT, fees=999_999)
    res = _connect(block, coins, T_HEIGHT)
    assert (res.ok, res.reason) == (False, "bad-cb-amount")


def test_connect_block_in_block_chaining():
    """A tx may spend an output created earlier in the same block."""
    coins, funded = make_funded_view(1, kinds=("p2wpkh",), amount=COIN)
    w2 = Wallet("chain2", "p2wpkh")
    t1 = Tx(
        version=2,
        vin=[TxIn(funded[0].outpoint)],
        vout=[TxOut(COIN - 1000, w2.spk)],
        locktime=0,
    )
    funded[0].wallet.sign_input(t1, 0, funded[0].amount)
    from bitcoinconsensus_tpu.utils.blockgen import FundedOutput

    t2 = build_spend_tx(
        [FundedOutput(OutPoint(t1.txid, 0), w2, COIN - 1000)], fee=1000
    )
    block = build_block([t1, t2], T_HEIGHT, fees=2000)
    res = _connect(block, coins, T_HEIGHT)
    assert res.ok, res.reason
    # Out-of-order chaining must fail (Core validates txs in order).
    coins2, funded2 = make_funded_view(1, kinds=("p2wpkh",), amount=COIN)
    t1b = Tx(
        version=2,
        vin=[TxIn(funded2[0].outpoint)],
        vout=[TxOut(COIN - 1000, w2.spk)],
        locktime=0,
    )
    funded2[0].wallet.sign_input(t1b, 0, funded2[0].amount)
    t2b = build_spend_tx(
        [FundedOutput(OutPoint(t1b.txid, 0), w2, COIN - 1000)], fee=1000
    )
    block2 = build_block([t2b, t1b], T_HEIGHT, fees=2000)
    res2 = _connect(block2, coins2, T_HEIGHT)
    assert (res2.ok, res2.reason) == (False, "bad-txns-inputs-missingorspent")


def test_connect_block_mixed_families_with_taproot():
    # 6 inputs cycle all 4 kinds incl. p2tr; their ~10 curve checks stay
    # on the 16-lane rung.
    coins, funded = make_funded_view(6)
    txs = [
        build_spend_tx(funded[0:2], fee=1000),
        build_spend_tx(funded[2:4], fee=1000),
        build_spend_tx(funded[4:6], fee=1000),
    ]
    block = build_block(txs, T_HEIGHT, fees=3000)
    res = _connect(block, coins, T_HEIGHT)
    assert res.ok, res.reason
    # Pre-taproot height: same block validates (taproot flag off — anyone
    # can spend the v1 outputs) but segwit v0 signatures still checked.
    coins2, funded2 = make_funded_view(6)
    block2 = build_block(txs, HEIGHT, fees=3000)
    res2 = _connect(block2, coins2, HEIGHT)
    assert res2.ok, res2.reason
