"""The comparison that decides `correct`, against the host oracle.

Truth is known by construction: the generator knows which input it
corrupted. The host oracle (the pure-Python interpreter and the host
curve code: no native core, no batching, no cache, no device) is run
outside the window on a seeded sample plus every
corrupted input, and verdict, `Error` and `ScriptError` must agree three
ways: what the timed path answered, what the oracle says, what was built.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

Triple = Tuple[bool, int, Optional[int]]


def oracle_verdict(raw: bytes, index: int, outs, flags: int) -> Triple:
    """(ok, Error, ScriptError) of one input by the program's specification
    engine: the pure-Python interpreter with the host curve code, the
    steps of `api.verify_with_spent_outputs` without its native short cut.
    The timed path shares none of it (native interpreter, device curve)."""
    from bitcoinconsensus_tpu.api import Error
    from bitcoinconsensus_tpu.core.interpreter import (
        TransactionSignatureChecker,
        verify_script,
    )
    from bitcoinconsensus_tpu.core.script_error import ScriptError
    from bitcoinconsensus_tpu.core.sighash import PrecomputedTxData
    from bitcoinconsensus_tpu.core.tx import Tx, TxOut

    tx = Tx.deserialize(raw)
    spent = [TxOut(amount, spk) for amount, spk in outs]
    if len(spent) != len(tx.vin) or not 0 <= index < len(tx.vin):
        raise ValueError("the generator built an input the oracle cannot address")
    checker = TransactionSignatureChecker(
        tx, index, spent[index].value, PrecomputedTxData(tx, spent)
    )
    ok, script_err = verify_script(
        tx.vin[index].script_sig, spent[index].script_pubkey,
        tx.vin[index].witness, flags, checker,
    )
    if ok:
        return True, int(Error.ERR_OK), int(ScriptError.OK)
    return False, int(Error.ERR_SCRIPT), int(script_err)


def as_triple(res) -> Triple:
    """A `BatchResult` in the oracle's form."""
    se = res.script_error
    return bool(res.ok), int(res.error), None if se is None else int(se)


def sample_indices(n: int, always: Sequence[int], k: int, seed: int) -> List[int]:
    """`k` indices of range(n) drawn from the seed, plus every one of
    `always` (the corrupted inputs)."""
    rng = random.Random(f"oracle-sample/{seed}")
    picked = set(rng.sample(range(n), min(k, n)))
    picked.update(always)
    return sorted(picked)


def compare(
    got: Dict[int, Optional[Triple]],
    items: Dict[int, Tuple[bytes, int, list, int]],
    built_ok: Dict[int, bool],
) -> dict:
    """`got[i]` is what the timed path answered for input i (None: no
    answer); `items[i]` the oracle's arguments for the sampled inputs;
    `built_ok[i]` the verdict by construction, for every input. Returns
    the counts and the first few differences; `mismatches` 0 means equal."""
    bad: List[tuple] = []
    for i, want_ok in built_ok.items():
        g = got.get(i)
        if g is None or g[0] != want_ok:
            bad.append((i, "timed path vs construction", g, want_ok))
    for i, (raw, index, outs, flags) in items.items():
        want = oracle_verdict(raw, index, outs, flags)
        if want[0] != built_ok[i]:
            bad.append((i, "oracle vs construction", want, built_ok[i]))
        g = got.get(i)
        if g is not None and g != want:
            bad.append((i, "timed path vs oracle", g, want))
    return {
        "compared_by_construction": len(built_ok),
        "compared_with_oracle": len(items),
        "rejected_by_construction": sum(not v for v in built_ok.values()),
        "mismatches": len(bad),
        "limit": 0,
        "first": [repr(b) for b in bad[:3]],
    }
