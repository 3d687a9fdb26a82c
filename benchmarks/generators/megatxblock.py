"""One block whose one legacy transaction spends thousands of P2PKH coins
(mainnet block 364292's shape), the coins it spends and three corrupted
twins, from a seed.

The transaction is version 1, every input a P2PKH spend of a distinct coin
with an uncompressed 65-byte key and a DER signature under SIGHASH_ALL, one
P2PKH output, locktime 0. Every signature's digest is Core's pre-BIP143
`SignatureHash`: the whole transaction with the other inputs' scripts
blanked, 228 kB of it an input at the configuration's 5,569 inputs. The
configuration fixes every count; the seed picks keys, amounts, outpoints
and the corrupted inputs. Signatures are ground to 71 and 72 bytes in turn
(a signature of a random nonce is either, half the time each), so that the
transaction and the block weigh the same for every seed.

This file signs through midstates (the blanked prefix is hashed once); the
plain reference (`harness/sighashref.py`) never does, and every digest
signed here is held to the reference's own, which also gives the bytes a
connect has to hash: the sum of its preimages' lengths. The twins each end
their victim `EVAL_FALSE`: one bit of the signature flipped; the input
signed over a preimage in which the OTHER inputs' scripts were not blanked
(each carries the script it spends); the input signed under SIGHASH_ALL
and its hash-type byte then changed to SIGHASH_NONE (a valid encoding,
another digest).

The transaction's and the block's size, the block's sigop cost (by the
plain reference, `harness/sigopref.py`), the one dispatch its checks fit
and the bytes hashed are asserted here against the configuration's own
figures. Returns what `generators/block.py` returns, and `twins` as
`generators/multisigblock.py` does.
"""

from __future__ import annotations

import hashlib
import random
import struct
from typing import List

from bitcoinconsensus_tpu.core.tx import Tx
from bitcoinconsensus_tpu.utils.hashes import hash160, sha256d

from ..harness import ec, sighashref, sigopref
from . import chain

VERSION = 1
USES_SECONDS = False

MAX_BLOCK_BASE_SIZE = 1_000_000  # consensus.h before segwit; a quarter of MAX_BLOCK_WEIGHT since
SIGHASH_ALL, SIGHASH_NONE = 1, 2
SEQUENCE = struct.pack("<I", 0xFFFFFFFF)
SIG_SIZES = (71, 72)  # DER of (r, s) and the hash-type byte, input by input in turn
BLOCK_TIME = 1_436_500_000  # July 2015


def _p2pkh(key: bytes) -> bytes:
    return b"\x76\xa9\x14" + hash160(key) + b"\x88\xac"


def _sign(sk: int, digest: bytes, size: int, hash_type: int = SIGHASH_ALL) -> bytes:
    """`ec.sign_ecdsa`'s low-s signature and the hash-type byte, from the
    first of its nonces that gives `size` bytes."""
    m, counter = int.from_bytes(digest, "big") % ec.N, 0
    while True:
        k = int.from_bytes(hashlib.sha256(
            sk.to_bytes(32, "big") + digest + counter.to_bytes(4, "big")).digest(), "big") % ec.N
        counter += 1
        if not k:
            continue
        r = ec.g_mul(k)[0] % ec.N
        s = pow(k, -1, ec.N) * (m + r * sk) % ec.N
        if not r or not s:
            continue
        body = ec._der_int(r) + ec._der_int(min(s, ec.N - s))
        if len(body) + 3 == size:
            return b"\x30" + bytes([len(body)]) + body + bytes([hash_type])


def build(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    blk = config["block"]
    n, height, fee = int(blk["inputs"]), int(blk["height"]), int(blk["fee_sat"])
    if not 4 <= n < int(config["verifier"]["chunk"]):
        raise ValueError(f"{n} checks do not fit one dispatch of {config['verifier']['chunk']} lanes")
    tag = f"{config['name']}/megatxblock/{seed}"
    rng = random.Random(tag)
    lo, hi = blk["amount_sat"]
    amounts = [rng.randrange(lo, hi) for _ in range(n)]
    sks = [int.from_bytes(hashlib.sha256(f"{tag}/sk/{i}".encode()).digest(), "big") % (ec.N - 1) + 1
           for i in range(n)]
    keys = []
    for sk in sks:
        x, y = ec.g_mul(sk)
        keys.append(b"\x04" + x.to_bytes(32, "big") + y.to_bytes(32, "big"))
    if len(set(keys)) != n:
        raise ValueError("the transaction's keys are not all distinct")
    spks = [_p2pkh(k) for k in keys]
    outpoints = [hashlib.sha256(f"{tag}/op/{i}".encode()).digest() + struct.pack("<I", i & 0xFFFF)
                 for i in range(n)]
    pay_to = b"\x76\xa9\x14" + hashlib.sha256(f"{tag}/pay".encode()).digest()[:20] + b"\x88\xac"
    output = struct.pack("<q", sum(amounts) - fee) + bytes([len(pay_to)]) + pay_to

    # The preimage of input i under SIGHASH_ALL: head, inputs before i
    # blanked, input i with the script it spends, inputs after i blanked,
    # tail. The blanked prefix grows by one input a step and is hashed once.
    head = struct.pack("<i", 1) + sighashref.compact_size(n)
    tail = b"\x01" + output + struct.pack("<I", 0) + struct.pack("<i", SIGHASH_ALL)
    blank = [op + b"\x00" + SEQUENCE for op in outpoints]
    own = [op + bytes([len(spk)]) + spk + SEQUENCE for op, spk in zip(outpoints, spks)]
    after = memoryview(b"".join(blank))
    width = len(blank[0])
    digests: List[bytes] = []
    prefix = hashlib.sha256(head)
    for i in range(n):
        h = prefix.copy()
        h.update(own[i])
        h.update(after[width * (i + 1):])
        h.update(tail)
        digests.append(hashlib.sha256(h.digest()).digest())
        prefix.update(blank[i])
    sigs = [_sign(sk, d, SIG_SIZES[i % 2]) for i, (sk, d) in enumerate(zip(sks, digests))]

    def raw_tx(signatures: List[bytes]) -> bytes:
        ins = []
        for op, sig, key in zip(outpoints, signatures, keys):
            script_sig = bytes([len(sig)]) + sig + bytes([len(key)]) + key
            ins.append(op + bytes([len(script_sig)]) + script_sig + SEQUENCE)
        return b"".join([struct.pack("<i", 1), sighashref.compact_size(n), *ins,
                         b"\x01", output, struct.pack("<I", 0)])

    outs = list(zip(amounts, spks))
    raw = raw_tx(sigs)

    # Every digest signed above against the plain reference's, and the bytes
    # the reference hashed for them: what one connect has to hash.
    parsed = sigopref.parse_tx(raw)
    hashed = 0
    for i in range(n):
        digest, size = sighashref.signature_hash(parsed, i, spks[i], SIGHASH_ALL)
        if digest != digests[i]:
            raise ValueError(f"input {i}: the digest signed is not the plain reference's")
        hashed += size

    def mined(tx_raw: bytes) -> bytes:
        return chain.mine([Tx.deserialize(tx_raw)], height, fee, b"\x00" * 32, BLOCK_TIME).serialize()

    def twin(name: str, victim: int, sig: bytes) -> dict:
        if len(sig) != len(sigs[victim]):
            raise ValueError(f"twin {name}: the corrupted signature changed the block's size")
        bad = raw_tx(sigs[:victim] + [sig] + sigs[victim + 1:])
        return {"name": name, "victim": victim, "kind": "p2pkh", "error": "EVAL_FALSE",
                "block": mined(bad), "tx": {"index": 0, "raw": bad, "outs": outs}}

    flipped, unblanked, retyped = rng.sample(range(n), 3)
    sig = sigs[flipped]
    leaked = b"".join([head, *own, tail])  # every input with the script it spends
    twins = [
        twin("signature-bit", flipped, sig[:9] + bytes([sig[9] ^ 1]) + sig[10:]),
        twin("scripts-not-blanked", unblanked,
             _sign(sks[unblanked], sha256d(leaked), len(sigs[unblanked]))),
        twin("hash-type-changed", retyped, sigs[retyped][:-1] + bytes([SIGHASH_NONE])),
    ]

    block = mined(raw)
    coinbase = sigopref.parse_tx(block[81:len(block) - len(raw)])
    cost = sigopref.block_sigop_cost(coinbase, [(parsed, outs)])
    want = {"tx_bytes": len(raw), "block_bytes": len(block), "sigop_cost": cost,
            "sighash_bytes": hashed}
    stated = {name: int(blk[name]) for name in want}
    if want != stated:
        raise ValueError(f"the block built has {want}, the configuration says {stated}")
    if len(block) > MAX_BLOCK_BASE_SIZE:
        raise ValueError(f"the block is {len(block)} bytes, over the limit on its stripped size")

    return {
        "height": height,
        "block": block,
        "bad_block": twins[0]["block"],
        "victim": twins[0]["victim"],
        "coins": [(op[:32], struct.unpack("<I", op[32:])[0], amount, 1, False, spk)
                  for op, amount, spk in zip(outpoints, amounts, spks)],
        "txs": [{"raw": raw, "outs": outs}],
        "bad_tx": twins[0]["tx"],
        "tx_start": [0],
        "kinds": ["p2pkh"] * n,
        "unseen_txs": [],
        "n_inputs": n,
        "coinbase": block[81:len(block) - len(raw)],
        "twins": twins,
        **want,
    }
