"""`verify_batch()` — the TPU-era equivalent of Core's per-input fan-out.

The reference parallelizes block validation by pushing one `CScriptCheck`
per input onto a thread-pool queue (`checkqueue.h:29-163`,
`validation.cpp:2190`). The TPU-native design replaces thread-level
parallelism with *signature-level batching* using the checker-override seam
the reference itself provides (`DeferringSignatureChecker`,
`interpreter.h:275-301`; `CachingTransactionSignatureChecker`,
`script/sigcache.cpp:101-122`):

1. Every input's script runs on host with a `DeferringSignatureChecker`
   that records each curve operation (ECDSA / Schnorr / taproot-tweak) and
   optimistically reports success (encoding checks still run inline).
2. All recorded checks from all inputs — deduplicated, the in-batch
   analogue of Core's salted sig cache (`script/sigcache.cpp:22-122`) —
   resolve in one mixed device dispatch (`crypto/jax_backend.py`).
3. Any input whose optimistic guesses were wrong is RE-interpreted with
   the device results as an oracle; checks discovered by the corrected
   control flow (CHECKMULTISIG's cursor advance depends on each result,
   interpreter.cpp:1177-1205; OP_CHECKSIG pushes the bool,
   interpreter.cpp:1097; NULLFAIL, interpreter.cpp:365-366) go out as
   further batched dispatches until a fixpoint — e.g. a 2-of-3 multisig
   whose sigs belong to the lower keys converges in two rounds, all on
   device. A round cap falls back to the exact host checker.

Batch results are bit-identical to per-input `verify_with_flags` /
`verify_with_spent_outputs`, including `Error` codes and `ScriptError`s
(asserted by tests/test_batch.py).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..api import ConsensusError, Error
from ..core.flags import ALL_FLAG_BITS, LIBCONSENSUS_FLAGS, VERIFY_TAPROOT
from ..core.interpreter import (
    ScriptExecutionData,
    TransactionSignatureChecker,
    verify_script,
)
from ..core.script_error import ScriptError
from ..core.serialize import SerializationError
from ..core.sighash import PrecomputedTxData
from ..core.tx import Tx, TxOut
from ..crypto.jax_backend import (
    _CHECKS_TOTAL,
    SigCheck,
    TpuSecpVerifier,
    default_verifier,
)
from .. import native_bridge
from ..obs import counter as _obs_counter
from ..obs import gauge as _obs_gauge
from ..obs import histogram as _obs_histogram
from ..obs import span as _span
from ..resilience import faults as _faults
from ..resilience import guards as _guards
from ..utils.gcpause import gc_paused
from ..utils.profiling import phases_of
from .sigcache import (
    ScriptExecutionCache,
    SigCache,
    default_script_cache,
    default_sig_cache,
)

__all__ = ["BatchItem", "BatchResult", "verify_batch", "verify_batch_stream"]

# Batch-driver telemetry (README "Observability"). All updates are host
# side and integer-valued — this module is under the host AST lint, which
# bans float literals and clock reads; timing flows through obs spans (the
# one sanctioned clock reader).
_BATCH_SIZE = _obs_histogram(
    "consensus_batch_size",
    "items per verify_batch call",
    buckets=(1, 8, 64, 512, 4096, 32768),
)
_BATCH_ITEMS = _obs_counter(
    "consensus_batch_items_total", "inputs submitted to verify_batch"
)
_BATCH_RESULTS = _obs_counter(
    "consensus_batch_results_total",
    "verify_batch results by outcome",
    ("outcome",),
)
_STREAM_WINDOW = _obs_gauge(
    "consensus_pipeline_stream_window",
    "stream handles concurrently in flight in verify_batch_stream "
    "(begun, not yet finished) — the pipeline's realized overlap depth",
)
_FIXPOINT_ROUNDS = _obs_histogram(
    "consensus_fixpoint_rounds",
    "oracle re-interpretation rounds needed per batch fixpoint",
    buckets=(1, 2, 3, 4, 6, 8, 12, 24),
)
_REINTERPRETED = _obs_counter(
    "consensus_fixpoint_reinterpreted_inputs_total",
    "inputs interpreted again in a fixpoint round after the first (an "
    "optimistic guess of theirs came back false)",
)
_SPEC_PAIRINGS = _obs_counter(
    "consensus_multisig_spec_pairings_total",
    "CHECKMULTISIG (signature, key) pairings pre-recorded ahead of the key "
    "walk that became deduplicated checks of their own",
)
_WALK_PAIRINGS = _obs_counter(
    "consensus_multisig_walk_pairings_total",
    "CHECKMULTISIG (signature, key) pairings the cursor walk tried in the "
    "interpretation whose verdict was returned: what Core's own walk verifies",
)
_SIGHASHES = _obs_counter(
    "consensus_sighash_total",
    "ECDSA message digests the native interpreter hashed (computed) or read "
    "again from a CHECKMULTISIG's record of its signatures (reused)",
    ("result",),
)
_SIGHASH_BYTES = _obs_counter(
    "consensus_sighash_bytes_total",
    "bytes the native interpreter fed to SHA-256 for the ECDSA message "
    "digests it computed, by kind (legacy: the whole transaction an input, "
    "less what a resumed digest's starting state had absorbed; bip143)",
    ("kind",),
)
_SIGHASH_SECONDS = _obs_counter(
    "consensus_sighash_seconds_total",
    "thread seconds the native interpreter spent building and hashing "
    "those preimages, summed over its workers, by kind",
    ("kind",),
)
_SIGHASH_TEMPLATES = _obs_counter(
    "consensus_sighash_template_total",
    "a transaction's blanked legacy serialisation, which its legacy "
    "digests are hashed from as spans: built (laid down, once a "
    "transaction whatever the thread count, once more where SIGHASH_NONE "
    "or SIGHASH_SINGLE is also signed), served (digests hashed from one) "
    "and resumed (served digests that started from one of the SHA-256 "
    "states it keeps every 4,096 bytes of their shared prefix)",
    ("event",),
)
# The native stage clock (native/interp.hpp), raised once a fixpoint from the
# session's table and once a block from the parsed block's.
_NATIVE_STAGES = _obs_counter(
    "consensus_native_stage_seconds_total",
    "seconds the native core spent beneath the ctypes boundary, by the call "
    "and its serial stage: interpret (setup, workers, merge), lanes (order, "
    "shards), digests (shards), accounting (decide, fill, copy); a call's "
    "stages tile it",
    ("call", "stage"),
)
_FAN_OUT = _obs_counter(
    "consensus_fan_out_seconds_total",
    "what the native core's thread fan-outs say of themselves, by the "
    "session call (interpret, lanes, digests): wall (entry to joined), held "
    "(the width times wall), sum and max (the workers' busy seconds summed "
    "and the slowest's), start_lag (entry to the latest worker's first "
    "instruction), tail (the last worker's end to joined)",
    ("call", "stat"),
)


def raise_native_stages(read) -> None:
    """One read of a handle's stage clock (`native_bridge.NativeStages`)
    into the registry's two families."""
    for (call, stage), (seconds, _calls) in read.stages.items():
        _NATIVE_STAGES.inc(seconds, call=call, stage=stage)
    for (call, stat), seconds in read.fans.items():
        _FAN_OUT.inc(seconds, call=call, stat=stat)


_TAPROOT_HASHES = _obs_counter(
    "consensus_taproot_hash_total",
    "taproot hashes the native interpreter made: BIP 341 message digests "
    "(sighash) and the commitment's TapLeaf, TapBranch and TapTweak hashes",
    ("what",),
)
_EXACT_FALLBACK = _obs_counter(
    "consensus_exact_fallback_total",
    "inputs resolved by the exact host checker at the round cap",
)
_UNIQ_CHECKS = _obs_counter(
    "consensus_uniq_checks_total",
    "deduplicated curve checks discovered (uniq-list growth, index mode)",
)
_PREP_LANES = _obs_counter(
    "consensus_prep_lanes_total",
    "lanes prepped by nat_session_uniq_lanes, by whether the call sharded "
    "them over worker threads or ran serial (too few lanes, or one thread)",
    ("mode",),
)
# Shared with crypto/jax_backend.py: exceptional device lanes resolved
# exactly on host, whichever driver flags them.
_HOST_FIXUPS = _obs_counter(
    "consensus_host_fixup_total",
    "exceptional device lanes resolved exactly on host",
)
# Reject-reason counters are shared with the per-input API entry points
# (same registry names -> one process-wide view across both paths).
_VERIFY_REJECTS = _obs_counter(
    "consensus_verify_reject_total",
    "verify rejections by transport Error code (api + batch paths)",
    ("code",),
)
_SCRIPT_REJECTS = _obs_counter(
    "consensus_script_reject_total",
    "script-level rejections by ScriptError code (api + batch paths)",
    ("script_error",),
)


def _record_batch_results(out: List["BatchResult"]) -> None:
    """Aggregate result counters locally, then publish once per batch —
    bounded lock traffic no matter the batch size."""
    ok_n = 0
    rejects: Dict[Tuple[str, Optional[str]], int] = {}
    for r in out:
        if r.ok:
            ok_n += 1
        else:
            serr = (
                r.script_error.name
                if r.script_error is not None
                and r.script_error != ScriptError.OK
                else None
            )
            key = (r.error.name, serr)
            rejects[key] = rejects.get(key, 0) + 1
    if ok_n:
        _BATCH_RESULTS.inc(ok_n, outcome="ok")
    for (code, serr), n in rejects.items():
        _BATCH_RESULTS.inc(n, outcome="reject")
        _VERIFY_REJECTS.inc(n, code=code)
        if serr is not None:
            _SCRIPT_REJECTS.inc(n, script_error=serr)


@dataclass
class BatchItem:
    """One input verification request.

    `spent_outputs` (all prevouts of the tx, in input order) unlocks the
    taproot path; with only `spent_output_script`+`amount` the item has the
    same reach as the reference C ABI (SURVEY §3.2).
    """

    spending_tx: bytes
    input_index: int
    flags: int
    spent_output_script: Optional[bytes] = None
    amount: int = 0
    spent_outputs: Optional[Sequence[Tuple[int, bytes]]] = None


@dataclass(frozen=True)
class BatchResult:
    """One input's verdict. Frozen, so every passing input can be the one
    `success()` instance: a 6,000-input block costs no result object of
    its own unless an input fails."""

    ok: bool
    error: Error
    script_error: Optional[ScriptError] = None

    @staticmethod
    def success() -> "BatchResult":
        return _SUCCESS


_SUCCESS = BatchResult(True, Error.ERR_OK, ScriptError.OK)


class DeferringSignatureChecker(TransactionSignatureChecker):
    """Records curve checks and answers from a known-results oracle,
    optimistically succeeding on unknowns; the sighash and all encoding
    checks still run inline (they are host work by design).

    With an empty oracle this is the plain optimistic first pass. With
    device results fed back in, re-interpretation resolves control flow
    exactly where earlier guesses were wrong — the CHECKMULTISIG cursor
    (interpreter.cpp:1177-1205) tries sig/key pairs in order, so a 2-of-3
    whose sigs belong to lower keys discovers the true pairing over a few
    oracle rounds, each a batched device dispatch instead of host EC math.
    `unknown` counts oracle misses: zero means the produced verdict is
    exact."""

    def __init__(self, tx, n_in, amount, txdata, known=None):
        super().__init__(tx, n_in, amount, txdata)
        self.recorded: List[SigCheck] = []
        self.known = known if known is not None else {}
        self.unknown = 0

    def _resolve(self, kind: str, data: Tuple) -> bool:
        res = self.known.get((kind, data))
        if res is None:
            self.unknown += 1
            self.recorded.append(SigCheck(kind, data))
            return True
        return res

    def verify_ecdsa(self, sig_der: bytes, pubkey: bytes, sighash: bytes) -> bool:
        return self._resolve("ecdsa", (pubkey, sig_der, sighash))

    def verify_schnorr(self, sig64: bytes, pubkey32: bytes, sighash: bytes) -> bool:
        return self._resolve("schnorr", (pubkey32, sig64, sighash))

    def verify_taproot_tweak(self, q: bytes, parity: int, p: bytes, t: bytes) -> bool:
        return self._resolve("tweak", (q, parity, p, t))


@dataclass
class _Prepared:
    result: Optional[BatchResult] = None  # set when failed before batching
    tx: Optional[Tx] = None
    txdata: Optional[PrecomputedTxData] = None
    script_pubkey: bytes = b""
    amount: int = 0
    optimistic: Optional[Tuple[bool, ScriptError]] = None
    checks: List[SigCheck] = field(default_factory=list)
    ntx: Optional[object] = None  # native_bridge.NativeTx when native is on
    wtxid: Optional[bytes] = None


def _spent_memo_entry(item: BatchItem, spent_memo: Dict[int, Tuple]):
    """(List[TxOut], digest) for item.spent_outputs, memoized by the
    sequence's identity: a 10k-input tx shares ONE conversion + digest
    across its 10k items instead of an O(n²) per-item pass. Identity
    keying is safe within one verify_batch call (items hold the refs)."""
    key = id(item.spent_outputs)
    ent = spent_memo.get(key)
    if ent is None:
        outs = [TxOut(a, s) for a, s in item.spent_outputs]
        ent = (outs, ScriptExecutionCache.spent_digest(item.spent_outputs))
        spent_memo[key] = ent
    return ent


def _prepare(
    item: BatchItem,
    tx_cache: Dict[bytes, Tuple[Tx, bool]],
    txdata_cache: Dict[Tuple, PrecomputedTxData],
    spent_memo: Dict[int, Tuple],
    ntx_cache: Optional[Dict] = None,
) -> _Prepared:
    """Transport-level validation; mirrors bitcoinconsensus.cpp:79-101 check
    order (flags -> deserialize -> index -> size). PrecomputedTxData is
    built once per (tx, prevouts-digest) — the validation.cpp:1538-1549
    one-hash-pass-per-tx shape — and the digest keying means conflicting
    prevout lists for the same tx can never share a cache entry. With the
    native core on (ntx_cache given), parse + transport checks + hash
    precompute all happen in C++ and the Python Tx/PrecomputedTxData are
    never built (they are only consumed by the Python fallback engine)."""
    prep = _Prepared()
    allowed = ALL_FLAG_BITS if item.spent_outputs is not None else LIBCONSENSUS_FLAGS
    if item.flags & ~allowed:
        prep.result = BatchResult(False, Error.ERR_INVALID_FLAGS)
        return prep

    if ntx_cache is not None:
        if item.spent_outputs is not None:
            spent_outputs, digest = _spent_memo_entry(item, spent_memo)
            key = (item.spending_tx, digest)
        else:
            spent_outputs = None
            key = (item.spending_tx, None)
        if key in ntx_cache:
            ntx = ntx_cache[key]
        else:
            try:
                ntx = native_bridge.NativeTx(item.spending_tx)
            except ValueError:
                ntx = None
            if ntx is not None:
                # Precompute only with a LENGTH-VALID prevout list (one per
                # input); a mismatched list is rejected below with
                # ERR_TX_INDEX and the handle stays un-precomputed (it is
                # never interpreted — same key means same mismatch).
                if item.spent_outputs is None:
                    ntx.precompute()
                elif len(spent_outputs) == ntx.n_inputs:
                    ntx.set_spent_outputs(list(item.spent_outputs))
            ntx_cache[key] = ntx
        if ntx is None:
            prep.result = BatchResult(False, Error.ERR_TX_DESERIALIZE)
            return prep
        if item.input_index < 0 or item.input_index >= ntx.n_inputs:
            prep.result = BatchResult(False, Error.ERR_TX_INDEX)
            return prep
        if ntx.ser_size != len(item.spending_tx):
            prep.result = BatchResult(False, Error.ERR_TX_SIZE_MISMATCH)
            return prep
        if spent_outputs is not None:
            if len(spent_outputs) != ntx.n_inputs:
                prep.result = BatchResult(False, Error.ERR_TX_INDEX)
                return prep
            prep.script_pubkey = spent_outputs[item.input_index].script_pubkey
            prep.amount = spent_outputs[item.input_index].value
        else:
            if item.flags & VERIFY_TAPROOT:
                prep.result = BatchResult(False, Error.ERR_AMOUNT_REQUIRED)
                return prep
            prep.script_pubkey = item.spent_output_script or b""
            prep.amount = item.amount
        prep.ntx = ntx
        prep.wtxid = ntx.wtxid
        return prep

    try:
        cached = tx_cache.get(item.spending_tx)
        if cached is None:
            tx = Tx.deserialize(item.spending_tx)
            size_ok = len(tx.serialize()) == len(item.spending_tx)
            tx_cache[item.spending_tx] = (tx, size_ok)
        else:
            tx, size_ok = cached
        # Index before size, matching api._verify_input and the reference
        # (bitcoinconsensus.cpp:89-92): a tx with both trailing bytes AND an
        # out-of-range index must report ERR_TX_INDEX from every entry point.
        # nIn is unsigned in the reference ABI: negative is out-of-range,
        # never Python wraparound.
        if item.input_index < 0 or item.input_index >= len(tx.vin):
            prep.result = BatchResult(False, Error.ERR_TX_INDEX)
            return prep
        if not size_ok:
            prep.result = BatchResult(False, Error.ERR_TX_SIZE_MISMATCH)
            return prep
    except SerializationError:
        prep.result = BatchResult(False, Error.ERR_TX_DESERIALIZE)
        return prep

    if item.spent_outputs is not None:
        spent_outputs, digest = _spent_memo_entry(item, spent_memo)
        if len(spent_outputs) != len(tx.vin):
            prep.result = BatchResult(False, Error.ERR_TX_INDEX)
            return prep
        tkey = (id(tx), digest)
        txdata = txdata_cache.get(tkey)
        if txdata is None:
            txdata = PrecomputedTxData(tx, spent_outputs)
            txdata_cache[tkey] = txdata
        prep.txdata = txdata
        prep.script_pubkey = spent_outputs[item.input_index].script_pubkey
        prep.amount = spent_outputs[item.input_index].value
    else:
        if item.flags & VERIFY_TAPROOT:
            prep.result = BatchResult(False, Error.ERR_AMOUNT_REQUIRED)
            return prep
        tkey = (id(tx), None)
        txdata = txdata_cache.get(tkey)
        if txdata is None:
            txdata = PrecomputedTxData(tx)
            txdata_cache[tkey] = txdata
        prep.txdata = txdata
        prep.script_pubkey = item.spent_output_script or b""
        prep.amount = item.amount
    prep.tx = tx
    prep.wtxid = tx.wtxid
    return prep


def _idx_threads() -> int:
    """Fan-out width of the native index-mode path: interpretation (the
    checkqueue.h:29-163 axis) and the resolve round's lane prep and cache
    digests (the C calls release the GIL). Overridable via
    BITCOINCONSENSUS_TPU_THREADS; single-core hosts stay serial."""
    env = os.environ.get("BITCOINCONSENSUS_TPU_THREADS", "")
    if env:
        return max(1, int(env))
    return max(1, os.cpu_count() or 1)


class _UniqState:
    """Per-fixpoint resolution state over the session's uniq list:
    `val[i]` is entry i's verdict (every entry resolves in the round that
    discovers it, so the array is complete up to its length)."""

    __slots__ = ("val",)

    def __init__(self):
        self.val = np.zeros(0, dtype=bool)


def _accept_mask(state: _UniqState, rec_idx: np.ndarray, bounds,
                 unk) -> np.ndarray:
    """Per-input acceptance after a resolve round: input k's verdict is
    exact when it had no oracle misses (unk == 0) or every miss resolved
    TRUE (the optimistic assumption matched reality).
    Vectorized over the rec_idx slices via one cumulative sum — the
    per-input Python loop this replaces was ~10% of block-replay host
    time."""
    unk = np.asarray(unk)
    out = unk == 0
    if len(rec_idx) and not out.all():
        have = state.val[rec_idx].astype(np.int64)
        b = np.asarray(bounds, dtype=np.int64)
        cs = np.concatenate([np.zeros(1, np.int64), np.cumsum(have)])
        out = out | ((cs[b[1:]] - cs[b[:-1]]) == (b[1:] - b[:-1]))
    return out


def _dispatch_uniq(nsess, verifier, sig_cache, state: _UniqState):
    """Async half of the uniq resolve round: salted sig-cache probe first
    (success-only skip, script/sigcache.cpp:22-122), then packed kernel
    lanes prepped IN the session (no check bytes cross the bridge) and
    one in-flight device dispatch per chunk. Returns an opaque round
    record for `_settle_uniq` — nothing is synchronized here, so the
    caller can run host work (the NEXT batch's interpretation) while the
    lanes are on the wire. Returns None when no new uniq entries exist.

    Dispatch policy note: every unresolved entry resolves each round —
    INCLUDING the speculative CHECKMULTISIG pairings no rec_idx
    references. Deferring the speculative entries to a contingent second
    dispatch was measured and rejected: Core's CHECKMULTISIG cursor walks
    keys top-down (interpreter.cpp:1177-1205), so even a consensus-
    ordered m-of-n spend guesses a FALSE pairing first and the
    re-interpretation needs the pre-recorded pairings known — they are
    the main verdict path, not insurance, and deferring them bought a
    second 10k-lane device round-trip on the multisig benchmark."""
    U = nsess.uniq_count()
    lo = len(state.val)
    if U == lo:
        return None
    _UNIQ_CHECKS.inc(U - lo)
    grow = np.arange(lo, U, dtype=np.int32)
    n_threads = _idx_threads()
    with verifier.phases("host_prep"):
        raw = nsess.uniq_digests(sig_cache._salt, grow, n_threads).tobytes()
    state.val = np.concatenate([state.val, np.zeros(U - lo, dtype=bool)])

    if len(sig_cache) == 0 and _faults.active() is None:
        miss = grow  # cold cache: every probe misses
    else:
        with verifier.phases("sig_probe"):
            hit = sig_cache.contains_keys(raw, U - lo)
            if _guards.audit_cache_hits():
                # Audit mode (resilience): a hit certifies a past success,
                # but a poisoned entry certifies nothing — re-verify on
                # the exact oracle and evict entries proven wrong.
                for j in np.nonzero(hit)[0].tolist():
                    if not nsess.uniq_host_verify(lo + j):
                        _guards.CACHE_POISON_CAUGHT.inc(cache="sig")
                        sig_cache.discard_key(raw[32 * j : 32 * j + 32])
                        hit[j] = False
            state.val[lo:] = hit
            miss = grow[~hit]
    pending = []
    cap = verifier.lane_capacity
    for s in range(0, len(miss), cap):
        sub = miss[s : s + cap]
        with verifier.phases("host_prep"):
            lanes = nsess.uniq_lanes(sub, verifier.pad(len(sub)), n_threads)
        sharded = native_bridge.prep_shards(len(sub), n_threads) > 1
        _PREP_LANES.inc(len(sub), mode="sharded" if sharded else "serial")
        pending.append((verifier.dispatch_lanes(lanes, len(sub)), sub))
    return grow, raw, pending


def _settle_uniq(nsess, verifier, sig_cache, state: _UniqState,
                 round_rec) -> None:
    """Settle half of the uniq resolve round: every in-flight ticket
    resolves through the verifier's guards (exceptional or contained
    lanes land on nat_session_uniq_host_verify), verdicts publish into
    the native oracle and successes into the salted sig cache."""
    if round_rec is None:
        return
    grow, raw, pending = round_rec
    for pend, sub in pending:
        okv, needs = verifier.sync_lanes(pend, len(sub))
        okv = np.array(okv, dtype=bool, copy=True)
        if needs is not None and needs.any():
            with verifier.phases("host_fixup"):
                fix = np.nonzero(needs)[0]
                _HOST_FIXUPS.inc(len(fix))
                for t in fix:
                    r = nsess.uniq_host_verify(int(sub[t]))
                    okv[t] = r
                    if not r:
                        verifier._fixup_failed = True
        with verifier.phases("sig_insert"):
            state.val[sub] = okv
            # success-only, like the reference; raw's row j is entry grow[0]+j
            sig_cache.add_keys(raw, (sub - grow[0])[okv])

    with verifier.phases("publish"):
        nsess.publish_uniq(grow, state.val[grow].astype(np.int32))


def _resolve_uniq(nsess, verifier, sig_cache, state: _UniqState) -> None:
    """One synchronous uniq resolve round (dispatch + settle back-to-back)."""
    _settle_uniq(nsess, verifier, sig_cache, state,
                 _dispatch_uniq(nsess, verifier, sig_cache, state))


class IdxFixpoint:
    """The deferral fixpoint both index-mode drivers share
    (`_verify_batch_idx` and models/validate.py `_NativeConnect` —
    ONE copy of the consensus-critical loop), split into an async `begin`
    and a settling `finish` so stream drivers can overlap batches.

    `begin()` interprets the pending inputs (`run_idx(pos) -> (ok, err,
    unk, rec_idx, bounds)`) and dispatches every newly-discovered uniq
    check, leaving the round's device lanes IN FLIGHT. `finish()` settles
    them, accepts inputs whose verdicts are exact (no misses, or every
    optimistic guess confirmed true), and runs any remaining rounds to
    the fixpoint; inputs still pending at the round cap go through
    `exact_fallback(idx) -> (ok, err_code)`. Verdicts live in two int32
    arrays indexed by input (`ok`, `err`; only the `live` rows are
    written) and the pending set is an index array, so a round moves
    masks, not one tuple an input. A stream driver calls batch
    N+1's `begin()` between batch N's `begin()` and `finish()`, so host
    interpretation runs while the previous batch is on the wire —
    `verify_batch_stream` is that driver for item batches, and
    models/validate.py `connect_block_stream` for whole blocks."""

    def __init__(
        self,
        nsess,
        verifier: TpuSecpVerifier,
        sig_cache: SigCache,
        live: Sequence[int],
        run_idx,
        exact_fallback,
        max_rounds: int = 24,  # > MAX_PUBKEYS_PER_MULTISIG cursor retries
        n_inputs: Optional[int] = None,  # rows of ok/err; default max(live)+1
    ):
        self.nsess = nsess
        self.verifier = verifier
        self._phases = phases_of(verifier)
        self.sig_cache = sig_cache
        self.run_idx = run_idx
        self.exact_fallback = exact_fallback
        self.max_rounds = max_rounds
        self._pending = np.asarray(live, dtype=np.int64)
        if n_inputs is None:
            n_inputs = int(self._pending.max()) + 1 if len(self._pending) else 0
        self.ok = np.zeros(n_inputs, dtype=np.int32)
        self.err = np.zeros(n_inputs, dtype=np.int32)
        self._state = _UniqState()
        self._rounds = 0
        self._in_flight = None  # (interp tuple, uniq round record)
        self.lanes: Optional[Dict[str, int]] = None  # by kind, at finish
        # CHECKMULTISIG pairings, at finish: `spec_pairings` pre-recorded
        # ahead of the walk, `walk_pairings` tried by the walk of the
        # interpretation each input's verdict was taken from
        self.multisig: Optional[Dict[str, int]] = None
        self.sighash_bytes: Optional[int] = None  # ECDSA preimages hashed, at finish
        # legacy templates `built` and digests `served` from one, at finish
        self.sighash_templates: Optional[Dict[str, int]] = None
        self._walk_pairings = 0
        self._round_walks = None  # the in-flight round's, by pending position

    def begin(self) -> None:
        """Start one round: interpret + dispatch, nothing synchronized."""
        if self._in_flight is not None or not len(self._pending):
            return
        if self._rounds >= self.max_rounds:
            return
        self._rounds += 1
        if self._rounds > 1:
            _REINTERPRETED.inc(len(self._pending))
        interp = self.run_idx(self._pending)  # the owner's `interpret` phase
        # what each input's CHECKMULTISIG walks tried, before the session's
        # next call overwrites it
        self._round_walks = self.nsess.call_walks(len(self._pending))
        rec = _dispatch_uniq(self.nsess, self.verifier, self.sig_cache,
                             self._state)
        self._in_flight = (interp, rec)

    def _settle_round(self) -> None:
        interp, rec = self._in_flight
        self._in_flight = None
        _settle_uniq(self.nsess, self.verifier, self.sig_cache,
                     self._state, rec)
        with self._phases("accept"):
            ok, err, unk, rec_idx, bounds = interp
            # exact verdict (unk == 0), or optimistic with every guess
            # confirmed true — equivalent to an exact pass
            accept = _accept_mask(self._state, rec_idx, bounds, unk)
            done = self._pending[accept]
            self.ok[done] = np.asarray(ok)[accept]
            self.err[done] = np.asarray(err)[accept]
            self._walk_pairings += int(self._round_walks[accept].sum())
            self._pending = self._pending[~accept]

    def abandon(self) -> None:
        """Settle-and-discard the in-flight round without running the
        fixpoint (the stream driver's generator-close path). The round's
        device tickets hold buffers and a backpressure slot in the
        verifier's in-flight queue, so they must settle even when nobody
        wants the verdicts; settle failures are already contained by the
        guards and irrelevant to a dead run."""
        self._pending = self._pending[:0]
        if self._in_flight is None:
            return
        _interp, rec = self._in_flight
        self._in_flight = None
        if rec is not None:
            _grow, _raw, pending = rec
            for pend, sub in pending:
                try:
                    self.verifier.sync_lanes(pend, len(sub))
                except Exception:
                    pass

    def release(self) -> None:
        """Free the native session now, timed as the `release` phase. The
        session's owner calls it after `finish()` (whose exact fallback is
        the session's last reader) or `abandon()`."""
        with self._phases("release"):
            self.nsess.release()

    def finish(self) -> Tuple[np.ndarray, np.ndarray]:
        """Settle the in-flight round, then loop to the fixpoint; returns
        the (ok, err) arrays."""
        if self._in_flight is not None:
            self._settle_round()
        while len(self._pending) and self._rounds < self.max_rounds:
            self.begin()
            if self._in_flight is None:  # defensive: begin refused
                break
            self._settle_round()
        _FIXPOINT_ROUNDS.observe(self._rounds)
        spec_pairings = self.nsess.spec_pairings()
        _SPEC_PAIRINGS.inc(spec_pairings)
        computed, reused = self.nsess.sighashes()
        _SIGHASHES.inc(computed, result="computed")
        _SIGHASHES.inc(reused, result="reused")
        self.sighash_bytes = 0
        for kind, (n_bytes, seconds) in self.nsess.sighash_work().items():
            _SIGHASH_BYTES.inc(n_bytes, kind=kind)
            _SIGHASH_SECONDS.inc(seconds, kind=kind)
            self.sighash_bytes += n_bytes
        self.sighash_templates = self.nsess.sighash_templates()
        for event, n in self.sighash_templates.items():
            _SIGHASH_TEMPLATES.inc(n, event=event)
        raise_native_stages(self.nsess.stages())
        self.lanes = self.nsess.lane_kinds()
        for kind, n in self.lanes.items():
            _CHECKS_TOTAL.inc(n, kind=kind)
        for what, n in self.nsess.taproot_hashes().items():
            _TAPROOT_HASHES.inc(n, what=what)
        if len(self._pending):  # round cap hit: exact host fallback
            _EXACT_FALLBACK.inc(len(self._pending))
        for idx in self._pending.tolist():
            self.ok[idx], self.err[idx] = self.exact_fallback(idx)
            # the fallback's walk is the one this verdict came from
            self._walk_pairings += int(self.nsess.call_walks(1).sum())
        _WALK_PAIRINGS.inc(self._walk_pairings)
        self.multisig = {"spec_pairings": spec_pairings,
                         "walk_pairings": self._walk_pairings}
        return self.ok, self.err


def run_idx_fixpoint(
    nsess,
    verifier: TpuSecpVerifier,
    sig_cache: SigCache,
    live: Sequence[int],
    run_idx,
    exact_fallback,
    max_rounds: int = 24,
    n_inputs: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Synchronous fixpoint (begin + finish back-to-back)."""
    run = IdxFixpoint(nsess, verifier, sig_cache, live, run_idx,
                      exact_fallback, max_rounds=max_rounds,
                      n_inputs=n_inputs)
    run.begin()
    return run.finish()


def _verify_batch_idx(
    items: Sequence[BatchItem],
    preps: List[_Prepared],
    nsess,
    verifier: TpuSecpVerifier,
    sig_cache: SigCache,
    script_cache: ScriptExecutionCache,
    script_keys: List[Optional[bytes]],
) -> List[BatchResult]:
    """Index-mode batch driver (the fast path of `verify_batch`).

    Same three phases as the legacy wire driver — deferring
    interpretation, one deduplicated device dispatch, oracle
    re-interpretation to a fixpoint — but the session keeps the deduped
    check list (`uniq`) in C++ and Python only ever moves int32 indices
    and packed lane arrays (native/nat.cpp nat_verify_inputs_idx + the
    uniq trio). Interpretation shards across `_idx_threads()` workers
    (checkqueue.h:29-163 shape). Results are bit-identical to the wire
    driver and the per-input API (tests/test_batch.py runs both paths)."""
    run = _idx_fixpoint_for(items, preps, nsess, verifier, sig_cache)
    final = None
    if run is not None:
        run.begin()
        final = run.finish()
        run.release()
    return _assemble_idx_results(preps, final, script_cache, script_keys)


def _idx_fixpoint_for(
    items: Sequence[BatchItem],
    preps: List[_Prepared],
    nsess,
    verifier: TpuSecpVerifier,
    sig_cache: SigCache,
) -> Optional[IdxFixpoint]:
    """Build the fixpoint runner for a prepared index-mode batch (None
    when every input already resolved via transport checks or the script
    cache). Shared by the synchronous driver and the stream driver."""
    live = [i for i, p in enumerate(preps) if p.result is None]
    if not live:
        return None
    n_threads = _idx_threads()

    def run_idx(pos: np.ndarray):
        pos = pos.tolist()
        with verifier.phases("interpret"):
            return nsess.verify_inputs_idx(
                [preps[i].ntx for i in pos],
                [items[i].input_index for i in pos],
                [preps[i].amount for i in pos],
                [preps[i].script_pubkey for i in pos],
                [items[i].flags for i in pos],
                n_threads=n_threads,
            )

    def exact_fallback(idx: int) -> Tuple[bool, int]:
        okx, err_code, _ = nsess.verify_input(
            preps[idx].ntx, items[idx].input_index, preps[idx].amount,
            preps[idx].script_pubkey, items[idx].flags,
            mode=native_bridge.NativeSession.MODE_EXACT,
        )
        return okx, err_code

    return IdxFixpoint(nsess, verifier, sig_cache, live, run_idx,
                       exact_fallback, n_inputs=len(preps))


def _assemble_idx_results(
    preps: List[_Prepared],
    final: Optional[Tuple[np.ndarray, np.ndarray]],
    script_cache: ScriptExecutionCache,
    script_keys: List[Optional[bytes]],
) -> List[BatchResult]:
    """Lay the fixpoint's (ok, err) arrays out per item (None: every input
    resolved before interpretation); the passing inputs' script-cache
    keys go in as one bulk insert, in item order."""
    if final is None:
        return [prep.result for prep in preps]
    ok, err = (a.tolist() for a in final)
    out: List[BatchResult] = []
    passed: List[bytes] = []
    for idx, prep in enumerate(preps):
        if prep.result is not None:
            out.append(prep.result)
        elif ok[idx]:
            if script_keys[idx] is not None:
                passed.append(script_keys[idx])
            out.append(_SUCCESS)
        else:
            out.append(
                BatchResult(False, Error.ERR_SCRIPT, ScriptError(err[idx]))
            )
    script_cache.add_keys(b"".join(passed))
    return out


def verify_batch(
    items: Sequence[BatchItem],
    verifier: Optional[TpuSecpVerifier] = None,
    sig_cache: Optional[SigCache] = None,
    script_cache: Optional[ScriptExecutionCache] = None,
) -> List[BatchResult]:
    """Verify many inputs with one TPU signature dispatch.

    Returns one `BatchResult` per item, bit-identical to the per-input API.
    The cross-batch caches (success-only, salted keys — the
    `script/sigcache.cpp` / `validation.cpp:1529-1536` production skip
    paths) default to the process-wide instances; pass fresh instances to
    isolate. Mempool→block replays skip interpretation and the device
    entirely on repeat batches.

    Cycle collection is paused for the duration (utils/gcpause.py): the
    driver's allocation churn otherwise triggers repeated full GC passes
    over the JAX runtime's heap — measured 12x on cached replays.
    """
    _BATCH_SIZE.observe(len(items))
    _BATCH_ITEMS.inc(len(items))
    with gc_paused(), _span("batch.verify_batch", n=len(items)):
        out = _verify_batch_impl(items, verifier, sig_cache, script_cache)
    _record_batch_results(out)
    return out


def verify_batch_stream(
    batches,
    verifier: Optional[TpuSecpVerifier] = None,
    sig_cache: Optional[SigCache] = None,
    script_cache: Optional[ScriptExecutionCache] = None,
    depth: int = 2,
):
    """Pipelined `verify_batch` over an iterable of item lists.

    Yields one result list per input batch, in order, bit-identical to
    calling `verify_batch` per batch — but with up to `depth` batches in
    flight: batch N+1's parse/probe/interpretation runs on the host while
    batch N's device lanes are on the wire, so a sustained stream pays
    the link latency once, not once per batch. The verifier's bounded
    in-flight queue still applies per dispatch (backpressure), and every
    ticket settles through the resilience guards — overlap never bypasses
    containment.

    Batches that cannot take the index-mode path (no native core, or a
    transport-failed parse without a native handle) fall back to a
    synchronous `verify_batch` for that batch; ordering is preserved.
    """
    if verifier is None:
        verifier = default_verifier()
    if sig_cache is None:
        sig_cache = default_sig_cache()
    if script_cache is None:
        script_cache = default_script_cache()
    depth = max(1, int(depth))
    window: List[tuple] = []
    phases = phases_of(verifier)

    def _begin(items):
        # The sweeps that end these two sections stay unphased: nothing reads
        # a batch's tiling, and a serving worker pays for every seam.
        with gc_paused(), _span("batch.stream_begin", n=len(items)):
            if native_bridge.available() and _idx_mode_enabled():
                nsess, preps, script_keys, _ = _prepare_and_probe(
                    items, script_cache, phases
                )
                if all(p.result is not None or p.ntx is not None
                       for p in preps):
                    _BATCH_SIZE.observe(len(items))
                    _BATCH_ITEMS.inc(len(items))
                    run = _idx_fixpoint_for(items, preps, nsess, verifier,
                                            sig_cache)
                    if run is not None:
                        run.begin()
                    return ("idx", run, preps, script_keys)
        # Synchronous fallback: full verify (its own metrics/spans).
        return ("done", verify_batch(items, verifier, sig_cache,
                                     script_cache))

    def _finish(handle):
        if handle[0] == "done":
            return handle[1]
        _tag, run, preps, script_keys = handle
        with gc_paused(), _span("batch.stream_finish", n=len(preps)):
            final = None
            if run is not None:
                final = run.finish()
                run.release()
            out = _assemble_idx_results(preps, final, script_cache,
                                        script_keys)
        _record_batch_results(out)
        return out

    try:
        for items in batches:
            window.append(_begin(items))
            _STREAM_WINDOW.set(len(window))
            while len(window) >= depth:
                yield _finish(window.pop(0))
                _STREAM_WINDOW.set(len(window))
        while window:
            yield _finish(window.pop(0))
            _STREAM_WINDOW.set(len(window))
    finally:
        # Consumer closed the generator mid-stream (GeneratorExit lands
        # at a yield above): begun batches still hold in-flight device
        # tickets — settle and discard them so buffers and backpressure
        # slots in the verifier's queue are not leaked.
        _abandon_stream_window(window)


def _abandon_stream_window(window: List[tuple]) -> None:
    """Settle-and-discard every begun-but-unfinished stream handle."""
    while window:
        handle = window.pop(0)
        if handle[0] == "idx" and handle[1] is not None:
            handle[1].abandon()
            handle[1].release()


def _prepare_and_probe(
    items: Sequence[BatchItem],
    script_cache: ScriptExecutionCache,
    phases,
):
    """Front half shared by the batch drivers: parse/prepare every item
    (native session when available) and probe the script-execution cache,
    as the verifier's `prepare` and `probe` phases.
    Returns (nsess, preps, script_keys, use_native)."""
    use_native = native_bridge.available()
    nsess = native_bridge.NativeSession() if use_native else None
    tx_cache: Dict[bytes, Tuple[Tx, bool]] = {}
    txdata_cache: Dict[Tuple, PrecomputedTxData] = {}
    spent_memo: Dict[int, Tuple] = {}
    ntx_cache: Optional[Dict] = {} if use_native else None
    with phases("prepare"):
        preps = [
            _prepare(item, tx_cache, txdata_cache, spent_memo, ntx_cache)
            for item in items
        ]

    # Script-execution cache probe: a hit certifies this exact
    # (wtxid, input, flags, prevouts) succeeded before — skip the
    # interpreter and the device outright (validation.cpp:1529-1536).
    script_keys: List[Optional[bytes]] = [None] * len(items)
    with phases("probe"):
        probe_idx: List[int] = []
        probe_parts: List[Tuple[bytes, ...]] = []
        for idx, (item, prep) in enumerate(zip(items, preps, strict=True)):
            if prep.result is not None or prep.wtxid is None:
                continue
            if item.spent_outputs is not None:
                digest = _spent_memo_entry(item, spent_memo)[1]
            else:
                digest = ScriptExecutionCache.spent_digest(
                    [(item.amount, item.spent_output_script or b"")]
                )
            probe_idx.append(idx)
            probe_parts.append(
                ScriptExecutionCache._parts(
                    prep.wtxid, item.input_index, item.flags, digest
                )
            )
        for idx, key in zip(probe_idx,
                            script_cache.keys_for_parts(probe_parts),
                            strict=True):
            script_keys[idx] = key
            if script_cache.contains_key(key):
                preps[idx].result = BatchResult.success()
    return nsess, preps, script_keys, use_native


def _idx_mode_enabled() -> bool:
    return os.environ.get("BITCOINCONSENSUS_TPU_IDX", "") not in ("0", "off")


def _verify_batch_impl(
    items: Sequence[BatchItem],
    verifier: Optional[TpuSecpVerifier],
    sig_cache: Optional[SigCache],
    script_cache: Optional[ScriptExecutionCache],
) -> List[BatchResult]:
    if verifier is None:
        verifier = default_verifier()
    if sig_cache is None:
        sig_cache = default_sig_cache()
    if script_cache is None:
        script_cache = default_script_cache()

    phases = phases_of(verifier)
    nsess, preps, script_keys, use_native = _prepare_and_probe(
        items, script_cache, phases
    )

    # Fast path: with the native core on, every prep either failed
    # transport checks (result set) or holds a native tx handle — the
    # whole batch runs the index-mode protocol (check bytes never cross
    # the bridge; Python sees int32 uniq indices only).
    # BITCOINCONSENSUS_TPU_IDX=0 forces the legacy wire driver (kept as
    # the executable spec; tests run the corpus through both).
    if (
        use_native
        and _idx_mode_enabled()
        and all(p.result is not None or p.ntx is not None for p in preps)
    ):
        return _verify_batch_idx(
            items, preps, nsess, verifier, sig_cache, script_cache, script_keys
        )

    # Phase 1: optimistic interpretation, recording curve checks. Inputs
    # the native engine parsed run in ONE batched C call (native/eval.hpp,
    # deferring mode — same protocol at C++ speed); this Python-engine
    # closure is the fallback for the rest and the executable spec.
    def interpret_deferring(item, prep) -> Tuple[bool, ScriptError, int, List[SigCheck]]:
        checker = DeferringSignatureChecker(
            prep.tx, item.input_index, prep.amount, prep.txdata, known=known
        )
        ok, err = verify_script(
            prep.tx.vin[item.input_index].script_sig,
            prep.script_pubkey,
            prep.tx.vin[item.input_index].witness,
            item.flags,
            checker,
        )
        return ok, err, checker.unknown, checker.recorded

    known: Dict[Tuple, bool] = {}
    with phases("interpret"):
        native_idx = [
            idx
            for idx, prep in enumerate(preps)
            if prep.result is None and prep.ntx is not None
        ]
        if native_idx:
            # ONE C call interprets every native-parsed input (the per-call
            # bridge overhead dominates a block-sized batch otherwise).
            ok_a, err_a, _unk_a, recs = nsess.verify_inputs(
                [preps[i].ntx for i in native_idx],
                [items[i].input_index for i in native_idx],
                [preps[i].amount for i in native_idx],
                [preps[i].script_pubkey for i in native_idx],
                [items[i].flags for i in native_idx],
                mode=native_bridge.NativeSession.MODE_DEFER,
            )
            for j, idx in enumerate(native_idx):
                preps[idx].optimistic = (
                    bool(ok_a[j]), ScriptError(int(err_a[j]))
                )
                preps[idx].checks = [SigCheck(k, d) for k, d in recs[j]]
        for item, prep in zip(items, preps, strict=True):
            if prep.result is not None or prep.ntx is not None:
                continue
            ok, err, _unk, checks = interpret_deferring(item, prep)
            prep.optimistic = (ok, err)
            prep.checks = checks

    # Speculative CHECKMULTISIG pairings recorded by the native engine ride
    # the same first dispatch (they are resolve-only: never part of any
    # prep.checks, so they cannot affect an optimistic verdict) — a
    # misaligned multisig then re-interprets against a fully-known oracle
    # instead of paying a second device round-trip.
    def drain_spec() -> List[SigCheck]:
        if nsess is None:
            return []
        spec = [SigCheck(k, d) for k, d in nsess.take_spec()]
        _SPEC_PAIRINGS.inc(len(spec))
        return spec

    # Phase 2: sig-cache probe, then one deduplicated device dispatch for
    # every remaining recorded check (sigcache.cpp:101-122 seam). Results
    # are published into the native oracle session as they land.
    pushed: set = set()

    def publish_known() -> None:
        if nsess is None:
            return
        fresh_entries = [
            (key[0], key[1], val)
            for key, val in known.items()
            if key not in pushed
        ]
        if fresh_entries:
            nsess.add_known_batch(fresh_entries)
            pushed.update((k, d) for k, d, _ in fresh_entries)

    def resolve(checks: Sequence[SigCheck]) -> None:
        """Fill `known` for every check: sig-cache probe (keys digested in
        one native call), then ONE deduplicated device dispatch; successes
        feed the cache."""
        todo: List[SigCheck] = []
        for chk in checks:
            key = (chk.kind, chk.data)
            if key in known:
                continue
            known[key] = False  # placeholder until probed/dispatched
            todo.append(chk)
        if todo:
            # Same observable as the index-mode uniq-list growth: how
            # many deduplicated checks this batch actually discovered.
            _UNIQ_CHECKS.inc(len(todo))
            cache_keys = sig_cache.keys_for_checks(todo)
            audit = _guards.audit_cache_hits()
            fresh: List[Tuple[SigCheck, bytes]] = []
            for chk, ck in zip(todo, cache_keys, strict=True):
                if sig_cache.contains_key(ck):
                    # Audit mode (resilience): re-verify the hit on
                    # the exact oracle; evict entries proven wrong.
                    if audit and not verifier._host_check(chk):
                        _guards.CACHE_POISON_CAUGHT.inc(cache="sig")
                        sig_cache.discard_key(ck)
                        fresh.append((chk, ck))
                    else:
                        known[(chk.kind, chk.data)] = True
                else:
                    fresh.append((chk, ck))
            if fresh:
                fresh_checks = [c for c, _ in fresh]
                try:
                    _faults.maybe_raise("batch.dispatch")
                    run_res = verifier.verify_checks(fresh_checks)
                except Exception:
                    # Driver-level dispatch fault: contain by resolving
                    # every check on the host-exact oracle (fail-closed
                    # — latency, never correctness).
                    _guards.CONTAINED.inc(site="batch.dispatch")
                    _guards.HOST_EXACT_LANES.inc(len(fresh_checks))
                    run_res = [
                        verifier._host_check(c) for c in fresh_checks
                    ]
                for (chk, ck), r in zip(fresh, run_res, strict=True):
                    known[(chk.kind, chk.data)] = bool(r)
                    if r:  # success-only insertion, like the reference
                        sig_cache.add_key(ck)
        publish_known()

    resolve([chk for prep in preps for chk in prep.checks] + drain_spec())

    # Phase 3: accept verdicts whose guesses all held; where any guess
    # failed, RE-interpret with the device results as an oracle —
    # newly-discovered checks (e.g. the true CHECKMULTISIG sig/key
    # pairing) go out as further batched dispatches until a fixpoint, so
    # control-flow-dependent scripts resolve without host EC math. A
    # round cap guards pathological scripts; the host checker is the
    # exact fallback.
    final: Dict[int, Tuple[bool, ScriptError]] = {}
    pending: List[int] = []
    for idx, prep in enumerate(preps):
        if prep.result is not None:
            continue
        if all(known[(c.kind, c.data)] for c in prep.checks):
            final[idx] = prep.optimistic
        else:
            pending.append(idx)

    max_rounds = 24  # > MAX_PUBKEYS_PER_MULTISIG cursor retries
    rounds = 1  # the optimistic pass above is round one
    for _round in range(max_rounds):
        if not pending:
            break
        rounds += 1
        _REINTERPRETED.inc(len(pending))
        new_checks: List[SigCheck] = []
        still: List[int] = []
        nat_pending = [i for i in pending if preps[i].ntx is not None]
        if nat_pending:
            ok_a, err_a, unk_a, recs = nsess.verify_inputs(
                [preps[i].ntx for i in nat_pending],
                [items[i].input_index for i in nat_pending],
                [preps[i].amount for i in nat_pending],
                [preps[i].script_pubkey for i in nat_pending],
                [items[i].flags for i in nat_pending],
                mode=native_bridge.NativeSession.MODE_DEFER,
            )
            for j, idx in enumerate(nat_pending):
                if int(unk_a[j]) == 0:
                    final[idx] = (bool(ok_a[j]), ScriptError(int(err_a[j])))
                else:
                    new_checks.extend(SigCheck(k, d) for k, d in recs[j])
                    still.append(idx)
        for idx in pending:
            if preps[idx].ntx is not None:
                continue
            item, prep = items[idx], preps[idx]
            ok, err, unknown, recorded = interpret_deferring(item, prep)
            if unknown == 0:
                final[idx] = (ok, err)  # every oracle read was exact
            else:
                new_checks.extend(recorded)
                still.append(idx)
        if not still:
            pending = []
            break
        resolve(new_checks + drain_spec())
        pending = still

    _FIXPOINT_ROUNDS.observe(rounds)
    if pending:  # round cap hit: exact host fallback
        _EXACT_FALLBACK.inc(len(pending))
    for idx in pending:
        item, prep = items[idx], preps[idx]
        if prep.ntx is not None:
            ok, err_code, _ = nsess.verify_input(
                prep.ntx, item.input_index, prep.amount, prep.script_pubkey,
                item.flags, mode=native_bridge.NativeSession.MODE_EXACT,
            )
            final[idx] = (ok, ScriptError(err_code))
            continue
        checker = TransactionSignatureChecker(
            prep.tx, item.input_index, prep.amount, prep.txdata
        )
        final[idx] = verify_script(
            prep.tx.vin[item.input_index].script_sig,
            prep.script_pubkey,
            prep.tx.vin[item.input_index].witness,
            item.flags,
            checker,
        )

    out: List[BatchResult] = []
    for idx, (_item, prep) in enumerate(zip(items, preps, strict=True)):
        if prep.result is not None:
            out.append(prep.result)
            continue
        ok, err = final[idx]
        if ok:
            if script_keys[idx] is not None:
                script_cache.add_key(script_keys[idx])
            out.append(BatchResult.success())
        else:
            out.append(BatchResult(False, Error.ERR_SCRIPT, err))
    return out
