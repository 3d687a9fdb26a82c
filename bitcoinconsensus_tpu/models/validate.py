"""Block-connect pipeline: the north-star replay driver (SURVEY §3.5).

TPU-era reshaping of the reference's `ConnectBlock` stack
(`validation.cpp:1946` → `CheckInputScripts` `:1516-1599` →
`CScriptCheck::operator()` `:1464-1468`): where Core fans per-input script
checks onto a thread-pool queue (`checkqueue.h:29-163`), this driver runs
every input's script through the deferring interpreter and resolves the
whole block's signature algebra in batched TPU dispatches via
`verify_batch` — signature-level batching replaces thread-level
parallelism.

Scope: the consensus rules that are functions of (block, UTXO view,
height) — input existence, coinbase maturity, value conservation, sigop
cost, script validity, coinbase reward. Chain-context rules that need
headers/median-time (BIP34 height-in-coinbase, BIP68 sequence locks,
nLockTime finality, difficulty retarget) sit above this layer, exactly as
they sit above `CheckInputScripts` in the reference.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import (
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Tuple,
    Union,
)

from ..api import Error
from ..core.block import (
    Block,
    MAX_BLOCK_SIGOPS_COST,
    POW_LIMIT_MAINNET,
    check_block,
    check_witness_commitment,
)
from ..core.flags import (
    VERIFY_P2SH,
    VERIFY_WITNESS,
    height_to_flags,
)
from ..core.script import (
    get_sig_op_count,
    is_p2sh,
    is_push_only,
    is_witness_program,
    iter_ops,
    witness_sig_ops,
)
from ..core.tx import COIN, MAX_MONEY, OutPoint, Tx, TxOut
from ..core.tx_check import WITNESS_SCALE_FACTOR
from ..crypto.jax_backend import TpuSecpVerifier
from ..obs import counter as _obs_counter
from ..obs import histogram as _obs_histogram
from ..obs import span as _span
from ..utils.gcpause import gc_paused
from ..utils.profiling import phases_of
from .batch import BatchItem, BatchResult, raise_native_stages, verify_batch
from .sigcache import ScriptExecutionCache, SigCache

__all__ = [
    "Coin",
    "CoinsView",
    "BlockUndo",
    "ConnectResult",
    "DisconnectResult",
    "connect_block",
    "connect_block_stream",
    "disconnect_block",
    "count_witness_sigops",
    "get_transaction_sigop_cost",
    "get_block_subsidy",
    "COINBASE_MATURITY",
]

COINBASE_MATURITY = 100  # consensus/consensus.h:19
SUBSIDY_HALVING_INTERVAL = 210_000  # chainparams.cpp mainnet

# Block-level telemetry (README "Observability"). The reason label reuses
# the reference's reject strings ("bad-txns-in-belowout", ...) verbatim.
_BLOCKS = _obs_counter(
    "consensus_blocks_total", "connect_block calls by result", ("result",)
)
_BLOCK_REJECTS = _obs_counter(
    "consensus_block_reject_total",
    "connect_block rejections by reason string",
    ("reason",),
)
# A block stream (connect_block_stream): how each block it took in ended,
# how many speculative applies were taken back, and how many blocks were
# begun and not yet finished each time one was begun.
_STREAM_BLOCKS = _obs_counter(
    "consensus_stream_blocks_total",
    "blocks a connect_block_stream took in, by how they ended "
    "(abandoned: begun behind a failed block or before an early close)",
    ("result",),
)
_STREAM_ROLLBACKS = _obs_counter(
    "consensus_stream_rollbacks_total",
    "speculative block applies a connect_block_stream undid",
)
_COIN_PROBES = _obs_counter(
    "consensus_coin_probes_total",
    "hash-table probes (a find, an insert or an erase by outpoint) the "
    "native accounting and apply of a connected block made, by table: the "
    "view, and pass 1's table of the block's own coins; and `undo`: the "
    "view's probes by a disconnect_block",
    ("table",),
)
# disconnect_block: how each call ended (validation.h DisconnectResult), and
# the coins the clean ones moved.
_DISCONNECTED = _obs_counter(
    "consensus_blocks_disconnected_total",
    "disconnect_block calls by result (ok, unclean, failed)",
    ("result",),
)
_UNDO_COINS = _obs_counter(
    "consensus_undo_coins_total",
    "coins a clean disconnect_block moved: spent coins restored to the "
    "view, outputs of the block removed from it",
    ("what",),
)
_STREAM_IN_FLIGHT = _obs_histogram(
    "consensus_stream_blocks_in_flight",
    "blocks begun and not yet finished in a connect_block_stream, "
    "observed at each begin (the one being begun included)",
    buckets=(1, 2, 3, 4, 6, 8, 16),
)


@dataclass
class Coin:
    """One unspent output + its creation metadata (coins.h Coin)."""

    out: TxOut
    height: int = 0
    coinbase: bool = False


class CoinsView:
    """Dict-backed UTXO set, the `CCoinsViewCache` role in ConnectBlock."""

    def __init__(self):
        self._map: Dict[Tuple[bytes, int], Coin] = {}

    def add(self, outpoint: OutPoint, coin: Coin) -> None:
        self._map[(outpoint.hash, outpoint.n)] = coin

    def add_tx(self, tx: Tx, height: int) -> None:
        cb = tx.is_coinbase()
        for n, out in enumerate(tx.vout):
            self._map[(tx.txid, n)] = Coin(out, height, cb)

    def get(self, outpoint: OutPoint) -> Optional[Coin]:
        return self._map.get((outpoint.hash, outpoint.n))

    def spend(self, outpoint: OutPoint) -> Optional[Coin]:
        return self._map.pop((outpoint.hash, outpoint.n), None)

    def __len__(self) -> int:
        return len(self._map)


def get_block_subsidy(height: int) -> int:
    """GetBlockSubsidy (validation.cpp:1246-1257)."""
    halvings = height // SUBSIDY_HALVING_INTERVAL
    if halvings >= 64:
        return 0
    return (50 * COIN) >> halvings


def count_witness_sigops(
    script_sig: bytes, script_pubkey: bytes, witness: List[bytes], flags: int
) -> int:
    """CountWitnessSigOps (interpreter.cpp:2074-2103)."""
    if not (flags & VERIFY_WITNESS):
        return 0
    assert flags & VERIFY_P2SH
    wp = is_witness_program(script_pubkey)
    if wp is not None:
        return witness_sig_ops(wp[0], wp[1], witness)
    if is_p2sh(script_pubkey) and is_push_only(script_sig):
        data = b""
        for _opcode, pushed in iter_ops(script_sig):
            data = pushed if pushed is not None else b""
        wp = is_witness_program(data)
        if wp is not None:
            return witness_sig_ops(wp[0], wp[1], witness)
    return 0


def get_transaction_sigop_cost(
    tx: Tx, spent_outputs: List[TxOut], flags: int
) -> int:
    """GetTransactionSigOpCost (consensus/tx_verify.cpp:125-147): legacy
    sigops ×4 + P2SH redeem sigops ×4 + witness sigops ×1."""
    cost = 0
    for txin in tx.vin:
        cost += get_sig_op_count(txin.script_sig, accurate=False)
    for txout in tx.vout:
        cost += get_sig_op_count(txout.script_pubkey, accurate=False)
    cost *= WITNESS_SCALE_FACTOR
    if tx.is_coinbase():
        return cost
    if flags & VERIFY_P2SH:
        p2sh = 0
        for txin, prevout in zip(tx.vin, spent_outputs, strict=True):
            if is_p2sh(prevout.script_pubkey) and is_push_only(txin.script_sig):
                data = b""
                for _opcode, pushed in iter_ops(txin.script_sig):
                    data = pushed if pushed is not None else b""
                p2sh += get_sig_op_count(data, accurate=True)
        cost += p2sh * WITNESS_SCALE_FACTOR
    for txin, prevout in zip(tx.vin, spent_outputs, strict=True):
        cost += count_witness_sigops(
            txin.script_sig, prevout.script_pubkey, txin.witness, flags
        )
    return cost


@dataclass
class BlockUndo:
    """undo.h CBlockUndo for a Python `CoinsView`: a transaction at a
    time, coinbase included (an empty list), the coins its inputs removed,
    in input order, each with its outpoint's key. The native view's record
    is `native_bridge.NativeBlockUndo`. `len` counts the coins."""

    spent: List[List[Tuple[Tuple[bytes, int], Coin]]] = field(default_factory=list)

    def __len__(self) -> int:
        return sum(len(tx) for tx in self.spent)


@dataclass
class DisconnectResult:
    """How a `disconnect_block` ended: `reason` is one of Core's three
    outcomes (validation.h DisconnectResult), `"ok"`, `"unclean"` or
    `"failed"`, and `ok` is the first. `restored` and `removed` count the
    coins a clean disconnect moved; the others move none."""

    ok: bool
    reason: str
    restored: int = 0
    removed: int = 0


@dataclass
class ConnectResult:
    ok: bool
    reason: Optional[str] = None
    fees: int = 0
    sigop_cost: int = 0
    input_results: Optional[List[BatchResult]] = None
    # The block's undo record, where the caller asked for one
    # (`want_undo`) and the block was connected: what `disconnect_block`
    # takes. None otherwise: a result with `ok` false carries no record.
    undo: Optional[object] = None

    @property
    def script_failures(self) -> List[int]:
        if not self.input_results:
            return []
        return [i for i, r in enumerate(self.input_results) if not r.ok]


def connect_block(
    block: Block,
    coins: CoinsView,
    height: int,
    flags: Optional[int] = None,
    verifier: Optional[TpuSecpVerifier] = None,
    check_pow: bool = True,
    check_scripts: bool = True,
    enforce_witness_commitment: Optional[bool] = None,
    pow_limit: int = POW_LIMIT_MAINNET,
    sig_cache: Optional[SigCache] = None,
    script_cache: Optional[ScriptExecutionCache] = None,
    want_undo: bool = False,
) -> ConnectResult:
    """Validate and apply one block against the UTXO view.

    Mirrors the consensus phases of `ConnectBlock` (validation.cpp:1946):

    1. context-free `CheckBlock` (+ witness commitment when the flag era
       includes WITNESS, matching IsWitnessEnabled gating);
    2. per tx: inputs present & mature, value conservation, accumulated
       sigop cost vs MAX_BLOCK_SIGOPS_COST (`validation.cpp:2155-2181`,
       `consensus/tx_verify.cpp:157-218` CheckTxInputs);
    3. all inputs' scripts through `verify_batch` — the signature-batched
       stand-in for the CCheckQueue fan-out (`validation.cpp:2190`);
    4. coinbase reward cap, then the view update (spend + add).

    The view is mutated only when every check passes. `flags` defaults to
    the mainnet `height_to_flags(height, extended=True)` schedule. With
    `want_undo` the apply keeps the coins it removes and an ok result
    carries them (`ConnectResult.undo`): the record `disconnect_block`
    needs to take the block off the tip again. It changes nothing else.

    Cycle collection is paused for the duration (utils/gcpause.py; see
    verify_batch) — the accounting loops over thousands of inputs
    otherwise pay repeated full GC passes over the JAX heap.

    With the native core on and a `NativeCoinsView`, the whole block
    layer (codec, merkle, CheckBlock, witness commitment, accounting,
    sigop costing, view update) runs in C++ and the script phase drives
    the index-mode session directly — the production replay path
    (`_connect_block_native`). Results are identical to the Python
    pipeline (tests/test_native_block.py replays both).

    For successive blocks use `connect_block_stream`: it yields the
    `ConnectResult`s this function would give one after the other, while
    it overlaps block N+1's host work with block N's device time behind a
    speculative view (its docstring lists the five things a stream
    guarantees; its cache hits may be fewer than a loop's, its verdicts
    not different). This function is the depth-1 case of the same two
    halves (`_NativeConnect.begin` and `.finish`), without speculation.
    """
    from .. import native_bridge

    if verifier is None and check_scripts:
        from ..crypto.jax_backend import default_verifier

        verifier = default_verifier()
    with gc_paused(phases_of(verifier)), \
            _span("block.connect", height=height) as sp:
        if (
            isinstance(coins, native_bridge.NativeCoinsView)
            and native_bridge.available()
        ):
            res = _connect_block_native(
                block, coins, height, flags, verifier, check_pow,
                check_scripts, enforce_witness_commitment, pow_limit,
                sig_cache, script_cache, sp, want_undo,
            )
        else:
            res = _connect_block_impl(
                block, coins, height, flags, verifier, check_pow,
                check_scripts, enforce_witness_commitment, pow_limit,
                sig_cache, script_cache, want_undo,
            )
        _count_block(res)
    return res


def _connect_block_native(
    block, coins, height, flags, verifier, check_pow, check_scripts,
    enforce_witness_commitment, pow_limit, sig_cache, script_cache, sp,
    want_undo=False,
) -> ConnectResult:
    """`connect_block` with the block layer in C++ (native/block.hpp) and
    the script phase on the index-mode session protocol: `_NativeConnect`
    begun and finished back to back, with no speculation (the view is
    written after the verdicts). `connect_block_stream` drives the same
    two halves with other blocks' halves in between. The lanes its
    fixpoint sent, by kind, its CHECKMULTISIG pairings (pre-recorded
    ahead of the walk; tried by the walk), the preimage bytes its ECDSA
    digests hashed and the legacy templates those were built, served and
    resumed from ride the `block.connect` span's record (`sp`)."""
    run = _NativeConnect(
        block, coins, height, flags, verifier, check_pow, check_scripts,
        enforce_witness_commitment, pow_limit, sig_cache, script_cache,
        want_undo,
    )
    run.begin()
    res = run.finish()
    if run.lanes is not None:
        sp.attrs.update({f"lanes_{k}": n for k, n in run.lanes.items()})
        sp.attrs.update(run.multisig)
        sp.attrs["sighash_bytes"] = run.sighash_bytes
        sp.attrs.update({f"sighash_template_{k}": n
                         for k, n in run.sighash_templates.items()})
    return res


class _NativeConnect:
    """One block's connect on the native path, cut in two at the seam
    `IdxFixpoint` has (models/batch.py): the one block driver behind
    `connect_block` and `connect_block_stream`.

    Phase map (validation.cpp:1946-2228). `begin()`: CheckBlock + witness
    commitment + BIP30/maturity/value/sigop accounting + per-tx hash
    precompute in three C calls, the script-cache probe, then
    `IdxFixpoint.begin()`, which interprets every input in one
    nat_verify_inputs_idx call and launches the deduped checks: lanes in
    flight, nothing synchronized. `finish()`: `IdxFixpoint.finish()`
    (settle, further rounds to the fixpoint), the script-cache insert and
    the result list, and the view update in one C call. Verdicts and
    reject reasons are identical to `_connect_block_impl`
    (tests/test_native_block.py).

    With `begin(speculate=True)` the view takes the block at the end of
    `begin`, before its verdicts, with an undo record (undo.h CBlockUndo),
    so that the next block's accounting can run while this block's lanes
    are on the device. The caller then owes the block one of `commit()`
    (after an ok `finish()`) or `rollback()` (after a failed one, or
    `abandon()` for a block whose verdicts nobody will read), and owes the
    view the order: blocks applied later are rolled back first.

    There is one apply, in `begin` or in `finish`. With `want_undo` it is
    made with a record wherever it is made, and an ok result carries the
    record (a speculative apply's, which `commit()` would drop, is kept).

    `result` is None until the connect has ended: a failure inside
    `begin()` sets it there (nothing was launched or applied)."""

    def __init__(
        self, block, coins, height, flags, verifier, check_pow,
        check_scripts, enforce_witness_commitment, pow_limit, sig_cache,
        script_cache, want_undo=False,
    ):
        if flags is None:
            flags = height_to_flags(height, extended=True)
        if check_scripts:
            if verifier is None:
                from ..crypto.jax_backend import default_verifier

                verifier = default_verifier()
            from .sigcache import default_script_cache, default_sig_cache

            if sig_cache is None:
                sig_cache = default_sig_cache()
            if script_cache is None:
                script_cache = default_script_cache()
        if enforce_witness_commitment is None:
            enforce_witness_commitment = bool(flags & VERIFY_WITNESS)
        self.block, self.coins, self.height, self.flags = block, coins, height, flags
        self.verifier = verifier
        self.check_pow, self.pow_limit = check_pow, pow_limit
        self.check_scripts = check_scripts
        self.enforce_witness_commitment = enforce_witness_commitment
        self.sig_cache, self.script_cache = sig_cache, script_cache
        self.want_undo = want_undo
        self.result: Optional[ConnectResult] = None
        self._nblk = None
        self._run = None  # the script phase's IdxFixpoint, once begun
        self.lanes = None  # the lanes it sent, by kind, once finished
        self.multisig = None  # its CHECKMULTISIG pairings, spec and walk, too
        self.sighash_bytes = None  # and the ECDSA preimage bytes it hashed
        self.sighash_templates = None  # from legacy templates built, served, resumed
        self._undo = None  # the speculative apply's undo record, until commit
        self._phase = phases_of(verifier)  # times nothing without a verifier

    def _parse(self):
        from .. import native_bridge

        block = self.block
        if isinstance(block, (bytes, bytearray)):
            with self._phase("parse"):
                return native_bridge.NativeBlock(bytes(block))
        # The cached parse is keyed on a cheap content fingerprint (header
        # bytes + per-tx txid/wtxid) so a Block mutated between calls is
        # re-serialized instead of validated stale. Mutating a Tx without
        # tx.invalidate_caches() leaves stale txids — which misleads the
        # Python pipeline identically, so the two paths cannot diverge.
        fp = (
            block.header.serialize(),
            tuple(tx.txid for tx in block.vtx),
            tuple(tx.wtxid for tx in block.vtx),
        )
        cached = getattr(block, "_native", None)
        if cached is not None and cached[0] == fp:
            return cached[1]
        with self._phase("parse"):
            nblk = native_bridge.NativeBlock(block.serialize())
        block._native = (fp, nblk)
        return nblk

    def begin(self, speculate: bool = False) -> None:
        import numpy as np

        from .. import native_bridge
        from .batch import IdxFixpoint, _idx_threads

        phase = self._phase
        flags, coins = self.flags, self.coins
        nblk = self._nblk = self._parse()

        with phase("block_check"):
            reason = nblk.check(self.check_pow, self.pow_limit)
            if not reason and self.enforce_witness_commitment:
                reason = nblk.check_witness_commitment()
            if reason:
                self.result = ConnectResult(False, reason)
                return

        with phase("accounting"):
            # With a script cache to probe, the same call makes its keys.
            salt = self.script_cache._salt if self.check_scripts else None
            (reason, fees, sigop_cost, tx_index, n_in, amounts, spk_offs,
             spk_blob) = nblk.accounting(coins, self.height, flags, salt)
            if reason:
                self.result = ConnectResult(False, reason)
                return
        self._fees, self._sigop_cost = fees, sigop_cost

        if self.check_scripts:
            verifier, script_cache = self.verifier, self.script_cache
            n = self._n = len(tx_index)
            with phase("probe"):
                raw_keys = nblk.script_keys().tobytes()
                if len(script_cache) == 0:  # cold cache: every probe misses
                    hit = np.zeros(n, dtype=bool)
                else:
                    hit = script_cache.contains_keys(raw_keys, n)
            self._raw_keys, self._hit = raw_keys, hit

            with phase("session_setup"):
                nsess = native_bridge.NativeSession()
                live = np.nonzero(~hit)[0]
                n_threads = _idx_threads()
                flags_a = np.full(n, flags, dtype=np.int32)

                # Raw NTx pointers, one per input: the txs are owned by the
                # (live) nblk, so the column outlasts every call below.
                tx_ptrs = nblk.tx_ptrs()[tx_index]

            def run_idx(pos):
                if len(pos) == n:  # common path: whole block, zero-copy
                    return nsess.verify_inputs_idx_raw(
                        tx_ptrs, n_in, amounts, spk_blob, spk_offs, flags_a,
                        n_threads,
                    )
                # A subset (script-cache hits left out, or a later fixpoint
                # round): gather its scriptPubKeys into one blob, vectorized.
                lens = spk_offs[pos + 1] - spk_offs[pos]
                sub_offs = np.zeros(len(pos) + 1, dtype=np.int64)
                np.cumsum(lens, out=sub_offs[1:])
                src = np.repeat(spk_offs[pos] - sub_offs[:-1], lens)
                src += np.arange(sub_offs[-1], dtype=np.int64)
                return nsess.verify_inputs_idx_raw(
                    tx_ptrs[pos], n_in[pos], amounts[pos], spk_blob[src],
                    sub_offs, flags_a[pos], n_threads,
                )

            def timed_run_idx(pos):
                with phase("interpret"):
                    return run_idx(pos)

            def exact_fallback(j: int) -> Tuple[bool, int]:
                t = int(tx_index[j])
                spk = spk_blob[int(spk_offs[j]) : int(spk_offs[j + 1])].tobytes()
                okx, err_code, _ = nsess.verify_input(
                    nblk.tx(t), int(n_in[j]), int(amounts[j]), spk, flags,
                    mode=native_bridge.NativeSession.MODE_EXACT,
                )
                return okx, err_code

            self._run = IdxFixpoint(
                nsess, verifier, self.sig_cache, live, timed_run_idx,
                exact_fallback, n_inputs=n,
            )
            self._run.begin()

        if speculate:
            with phase("apply"):
                self._undo = coins.apply_block(nblk, self.height, undo=True)
                self._count_probes()

    def finish(self) -> ConnectResult:
        if self.result is not None:
            return self.result
        input_results: Optional[List[BatchResult]] = None
        if self._run is not None:
            import numpy as np

            from ..core.script_error import ScriptError

            # The fixpoint keeps its verdict arrays: they end with it, in
            # `_free_block`, not at this frame's return.
            self._run.finish()
            self.lanes = self._run.lanes
            self.multisig = self._run.multisig
            self.sighash_bytes = self._run.sighash_bytes
            self.sighash_templates = self._run.sighash_templates
            self._run.release()
            with self._phase("results"):
                # ok/err are written on the live rows only; a hit passed
                # before.
                hit = self._hit
                passed = hit | (self._run.ok != 0)
                self.script_cache.add_keys(self._raw_keys, passed & ~hit)
                # Every passing input is the one frozen success instance;
                # only a failing input gets a result object of its own.
                input_results = [BatchResult.success()] * self._n
                failed = np.nonzero(~passed)[0].tolist()
                for j in failed:
                    input_results[j] = BatchResult(
                        False, Error.ERR_SCRIPT,
                        ScriptError(int(self._run.err[j])),
                    )
            if failed:
                self.result = ConnectResult(
                    False, "block-validation-failed", self._fees,
                    self._sigop_cost, input_results,
                )
                if self._undo is None:
                    self._free_block()
                return self.result
        record = self._undo if self.want_undo else None
        if self._undo is None:  # not applied speculatively in begin
            with self._phase("apply"):
                record = self.coins.apply_block(
                    self._nblk, self.height, undo=self.want_undo)
                self._count_probes()
            self._free_block()
        self.result = ConnectResult(
            True, None, self._fees, self._sigop_cost, input_results, record
        )
        return self.result

    def _count_probes(self) -> None:
        """One read a block, after its apply: the probes its accounting and
        that apply made (the parsed block counted them as it went), and
        beside them what the accounting spent in each of its native
        stages."""
        for table, n in self._nblk.coin_probes().items():
            _COIN_PROBES.inc(n, table=table)
        raise_native_stages(self._nblk.stages())

    def _free_block(self) -> None:
        """The `block_free` phase: what ends with the run, dropped after its
        last reader (the apply, or the undo of a speculative one): the
        parsed block, a speculative apply's undo record, and the fixpoint
        with its verdict arrays and the block's columns. A block parsed
        from raw bytes is freed here, `NativeBlock.__del__` (one cached on
        a `Block` object lives on with it); the arrays go last, so that
        the allocator's tidying after some 10^5 small frees (glibc
        consolidates at the next large one) is paid inside the phase."""
        with self._phase("block_free"):
            self._nblk = self._undo = None
            self._run = None

    def commit(self) -> None:
        """The speculative apply stands: drop its undo record (the result
        holds it on where the caller wanted it)."""
        self._free_block()

    def rollback(self) -> bool:
        """Take the speculative apply back; True when there was one."""
        if self._undo is None:
            return False
        undo, self._undo = self._undo, None
        with self._phase("undo"):
            self.coins.undo_block(self._nblk, undo)
        self._free_block()
        return True

    def abandon(self) -> bool:
        """For a block whose verdicts nobody will read: settle and
        discard its tickets (`IdxFixpoint.abandon`), insert nothing into a
        cache, take its speculative apply back. Returns `rollback()`'s."""
        if self._run is not None and self.result is None:  # begun, not finished
            self._run.abandon()
            self._run.release()
        return self.rollback()


def connect_block_stream(
    blocks: Iterable[Union[bytes, Block]],
    coins: CoinsView,
    start_height: int,
    *,
    depth: int = 2,
    flags: Union[None, int, Callable[[int], int]] = None,
    verifier: Optional[TpuSecpVerifier] = None,
    check_pow: bool = True,
    pow_limit: int = POW_LIMIT_MAINNET,
    sig_cache: Optional[SigCache] = None,
    script_cache: Optional[ScriptExecutionCache] = None,
    want_undo: bool = False,
) -> Iterator[ConnectResult]:
    """Connect successive blocks through one overlapped stream (initial
    block download: `ActivateBestChain -> ConnectTip -> ConnectBlock`).

    `blocks` are raw blocks (bytes) or `Block`s in height order; block `k`
    is connected at `start_height + k` under `height_to_flags(start_height
    + k, extended=True)`, or under `flags` (an int for every block, or a
    callable of the height). Yields one `ConnectResult` a block, in order,
    with up to `depth` blocks begun and not yet finished: while block N's
    lanes are on the device the host parses, checks, accounts and
    interprets block N+1. Block N+1's accounting reads coins that block N
    creates, so the view takes each block speculatively when it is begun
    and keeps the coins it spent (an undo record) until its verdicts are
    in. `connect_block` is the depth-1, unspeculative case of the same
    two halves (`_NativeConnect`). With `want_undo` every ok result
    carries that record (`ConnectResult.undo`, what `disconnect_block`
    takes) where the stream would drop it at the block's commit; it holds
    its coins by value, so it stays sound when a later block of the stream
    is rejected and rolled back.

    What a stream guarantees:

    1. Every yielded `ConnectResult` (`ok`, `reason`, `fees`,
       `sigop_cost`, every `input_results[i]`) equals what a loop of
       `connect_block` over the same blocks gives, at every `depth`.
    2. A block that fails, in either half, is yielded as that failure and
       is the last thing yielded. The blocks begun after it are abandoned
       (their tickets settled and discarded), their speculative applies
       and its own are undone newest first, and the view is the view
       after the last block yielded `ok`. No result of a block behind a
       failed one is ever yielded.
    3. Closing the generator early does the same to what was begun and
       not yielded: no ticket, buffer or backpressure slot stays held.
    4. The caches stay success-only, and a block's inserts happen in its
       own finish, never at begin. Block N+1 probes before block N
       inserts, so a stream may miss a cache hit that a loop would find:
       it then verifies again what the loop would have skipped, and
       verdicts cannot differ.
    5. The fail-closed guards are what they were: every ticket settles
       through resilience/inflight.py and the checksum and sentinel
       checks; a retry, demotion or containment during a stream gives the
       same verdicts and is counted as in `connect_block`.

    With a Python `CoinsView`, or without the native core, the blocks are
    connected one by one through `connect_block`, in order, as
    `verify_batch_stream` falls back to `verify_batch`.
    """
    from .. import native_bridge

    depth = max(1, int(depth))

    def flags_at(height: int) -> Optional[int]:
        return flags(height) if callable(flags) else flags

    if not (
        isinstance(coins, native_bridge.NativeCoinsView)
        and native_bridge.available()
    ):
        for k, block in enumerate(blocks):
            res = connect_block(
                block, coins, start_height + k, flags_at(start_height + k),
                verifier, check_pow, pow_limit=pow_limit,
                sig_cache=sig_cache, script_cache=script_cache,
                want_undo=want_undo,
            )
            _STREAM_BLOCKS.inc(result="ok" if res.ok else "reject")
            yield res
            if not res.ok:
                return
        return

    window: List[_NativeConnect] = []

    def begin(block, height: int) -> _NativeConnect:
        run = _NativeConnect(
            block, coins, height, flags_at(height), verifier, check_pow,
            True, None, pow_limit, sig_cache, script_cache, want_undo,
        )
        _STREAM_IN_FLIGHT.observe(len(window) + 1)
        with gc_paused(run._phase), _span("block.stream_begin", height=height):
            try:
                run.begin(speculate=True)
            except BaseException:
                run.abandon()  # whatever it launched settles; then re-raise
                raise
        return run

    def finish(run: _NativeConnect) -> ConnectResult:
        with gc_paused(run._phase), _span("block.stream_finish", height=run.height):
            res = run.finish()
            if res.ok:
                run.commit()
            _count_block(res)
            _STREAM_BLOCKS.inc(result="ok" if res.ok else "reject")
        return res

    def undo(run: _NativeConnect, abandoned: bool) -> None:
        with gc_paused(run._phase):
            if run.abandon():
                _STREAM_ROLLBACKS.inc()
        if abandoned:
            _STREAM_BLOCKS.inc(result="abandoned")

    def drop(runs: List[_NativeConnect]) -> None:
        """Abandon the blocks begun and never yielded, newest first."""
        while runs:
            undo(runs.pop(), abandoned=True)

    with _span("block.stream", depth=depth, start_height=start_height):
        try:
            source = iter(blocks)
            height = start_height
            more = True
            while more or window:
                # Fill: begin blocks until `depth` are in flight, the source
                # ends, or one fails where it is begun (nothing behind a
                # failed block is begun).
                while more and len(window) < depth:
                    block = next(source, None)
                    if block is None:
                        more = False
                        break
                    window.append(begin(block, height))
                    height += 1
                    more = window[-1].result is None
                if not window:
                    break
                res = finish(window[0])
                run = window.pop(0)
                if not res.ok:
                    drop(window)
                    undo(run, abandoned=False)  # last: it was begun first
                    yield res
                    return
                yield res
        except GeneratorExit:
            pass  # closed early: `finally` settles what was begun
        finally:
            drop(window)


def _count_block(res: ConnectResult) -> None:
    _BLOCKS.inc(result="ok" if res.ok else "reject")
    if not res.ok and res.reason:
        _BLOCK_REJECTS.inc(reason=res.reason)


def _connect_block_impl(
    block, coins, height, flags, verifier, check_pow, check_scripts,
    enforce_witness_commitment, pow_limit, sig_cache, script_cache,
    want_undo=False,
) -> ConnectResult:
    if flags is None:
        flags = height_to_flags(height, extended=True)
    if verifier is None and check_scripts:
        from ..crypto.jax_backend import default_verifier

        verifier = default_verifier()

    ok, reason = check_block(block, check_pow=check_pow, pow_limit=pow_limit)
    if not ok:
        return ConnectResult(False, reason)
    if enforce_witness_commitment is None:
        enforce_witness_commitment = bool(flags & VERIFY_WITNESS)
    if enforce_witness_commitment:
        ok, reason = check_witness_commitment(block)
        if not ok:
            return ConnectResult(False, reason)

    # Phase 2: inputs exist, maturity, values, sigop budget; gather the
    # spent outputs each tx needs (validation.cpp:1538-1549) without
    # mutating the view yet. Outputs created earlier in this same block are
    # spendable by later txs (the in-block overlay below).
    overlay: Dict[Tuple[bytes, int], Coin] = {}
    spent: set = set()
    per_tx_spent_outputs: List[List[TxOut]] = []
    fees = 0
    sigop_cost = 0

    # BIP30 guard (validation.cpp ConnectBlock's HaveCoin scan, run against
    # the start-of-block view before any spends): a tx whose outputs would
    # overwrite a still-unspent coin is rejected instead of silently
    # destroying it. In-block txid duplicates can't arise (identical txid
    # implies an identical tx, caught by the CVE-2012-2459 merkle check).
    for tx in block.vtx:
        for n in range(len(tx.vout)):
            if coins.get(OutPoint(tx.txid, n)) is not None:
                return ConnectResult(False, "bad-txns-BIP30")

    for tx in block.vtx:
        if tx.is_coinbase():
            per_tx_spent_outputs.append([])
            sigop_cost += get_transaction_sigop_cost(tx, [], flags)
            if sigop_cost > MAX_BLOCK_SIGOPS_COST:
                return ConnectResult(False, "bad-blk-sigops")
            overlay_tx_outputs(overlay, tx, height)
            continue
        spent_outputs: List[TxOut] = []
        value_in = 0
        for txin in tx.vin:
            key = (txin.prevout.hash, txin.prevout.n)
            if key in spent:
                return ConnectResult(False, "bad-txns-inputs-missingorspent")
            coin = overlay.get(key) or coins.get(txin.prevout)
            if coin is None:
                return ConnectResult(False, "bad-txns-inputs-missingorspent")
            if coin.coinbase and height - coin.height < COINBASE_MATURITY:
                return ConnectResult(False, "bad-txns-premature-spend-of-coinbase")
            if not (0 <= coin.out.value <= MAX_MONEY):
                return ConnectResult(False, "bad-txns-inputvalues-outofrange")
            value_in += coin.out.value
            # Accumulated value must stay in range too (CheckTxInputs,
            # consensus/tx_verify.cpp:157-218 MoneyRange(nValueIn)).
            if value_in > MAX_MONEY:
                return ConnectResult(False, "bad-txns-inputvalues-outofrange")
            spent_outputs.append(coin.out)
            spent.add(key)
        value_out = sum(o.value for o in tx.vout)
        if value_in < value_out:
            return ConnectResult(False, "bad-txns-in-belowout")
        fee = value_in - value_out
        fees += fee
        if not (0 <= fees <= MAX_MONEY):
            return ConnectResult(False, "bad-txns-fee-outofrange")
        sigop_cost += get_transaction_sigop_cost(tx, spent_outputs, flags)
        if sigop_cost > MAX_BLOCK_SIGOPS_COST:
            return ConnectResult(False, "bad-blk-sigops")
        per_tx_spent_outputs.append(spent_outputs)
        overlay_tx_outputs(overlay, tx, height)

    # Coinbase reward cap (validation.cpp:2222-2228).
    coinbase_out = sum(o.value for o in block.vtx[0].vout)
    if coinbase_out > fees + get_block_subsidy(height):
        return ConnectResult(False, "bad-cb-amount")

    # Phase 3: every input's script, one batched dispatch
    # (CheckInputScripts + CCheckQueue → verify_batch).
    input_results: Optional[List[BatchResult]] = None
    if check_scripts:
        items: List[BatchItem] = []
        for tx, spent_outputs in zip(block.vtx, per_tx_spent_outputs, strict=True):
            if tx.is_coinbase():
                continue
            raw = tx.serialize()
            outs = [(o.value, o.script_pubkey) for o in spent_outputs]
            for i in range(len(tx.vin)):
                items.append(
                    BatchItem(
                        spending_tx=raw,
                        input_index=i,
                        flags=flags,
                        spent_outputs=outs,
                    )
                )
        input_results = verify_batch(
            items,
            verifier=verifier,
            sig_cache=sig_cache,
            script_cache=script_cache,
        )
        if not all(r.ok for r in input_results):
            return ConnectResult(
                False, "block-validation-failed", fees, sigop_cost, input_results
            )

    # Phase 4: apply to the view (UpdateCoins, coins.cpp), keeping the
    # coins it removes where the caller wants the record.
    record = BlockUndo() if want_undo else None
    for tx in block.vtx:
        gone = []
        for txin in tx.vin:
            if not tx.is_coinbase():
                coin = coins.spend(txin.prevout)
                gone.append(((txin.prevout.hash, txin.prevout.n), coin))
        coins.add_tx(tx, height)
        if record is not None:
            record.spent.append(gone)
    return ConnectResult(True, None, fees, sigop_cost, input_results, record)


def disconnect_block(
    block: Union[bytes, Block],
    coins: CoinsView,
    undo,
    height: int,
    *,
    verifier: Optional[TpuSecpVerifier] = None,
) -> DisconnectResult:
    """Take the block at the tip of the view off it again (validation.cpp
    `DisconnectBlock`, the view half `DisconnectTip` flushes): `block` is
    the block that was connected at `height`, raw bytes or a `Block`, and
    `undo` the record its connect handed out (`ConnectResult.undo` under
    `want_undo`; a `NativeBlockUndo` for a `NativeCoinsView`, a
    `BlockUndo` for a Python `CoinsView`).

    Transactions last to first: every output of the transaction must be
    in the view exactly as the block made it (amount, script, height,
    coinbase flag) and is removed; every coin the transaction spent is put
    back, and nothing may stand where it goes. The outcomes are Core's:

    - `"ok"`: the view is the view before the block's connect, coin for
      coin.
    - `"unclean"`: the view is not where this block left it (an output
      gone or different, a coin in a restored coin's place): a block
      disconnected out of order, or twice. The view is untouched.
    - `"failed"`: the record is not this block's (its transaction count,
      a transaction's input count or a coin's outpoint disagrees). The
      view is untouched.

    Blocks are disconnected newest first. No cache is consulted or
    changed (Core's disconnect touches neither), nothing is verified and
    nothing reaches the device; `verifier` lends its phase clock only
    (`parse`, `undo_check`, `undo`, `block_free`). Unlike Core, outputs it
    would call unspendable are checked and removed too (this view holds
    them), and there are no BIP30 height exceptions.
    """
    from .. import native_bridge

    phase = phases_of(verifier)
    with _span("block.disconnect", height=height) as sp:
        if (
            isinstance(coins, native_bridge.NativeCoinsView)
            and native_bridge.available()
        ):
            if not isinstance(undo, native_bridge.NativeBlockUndo):
                raise TypeError("a NativeCoinsView takes a NativeBlockUndo")
            with phase("parse"):
                nblk = native_bridge.NativeBlock(
                    block if isinstance(block, (bytes, bytearray))
                    else block.serialize())
            with phase("undo_check"):
                matches = undo.matches(nblk)
            reason, probes, restored, removed = "failed", 0, 0, 0
            if matches:
                with phase("undo"):
                    reason, probes, restored, removed = (
                        coins.disconnect_block(nblk, undo, height, checked=True))
                _COIN_PROBES.inc(probes, table="undo")
            with phase("block_free"):
                nblk = None
        else:
            if not isinstance(undo, BlockUndo):
                raise TypeError("a Python CoinsView takes a BlockUndo")
            with phase("parse"):
                if isinstance(block, (bytes, bytearray)):
                    block = Block.deserialize(bytes(block))
            reason, restored, removed = _disconnect_block_impl(
                block, coins, undo, height, phase)
        res = DisconnectResult(reason == "ok", reason, restored, removed)
        _DISCONNECTED.inc(result=reason)
        if res.ok:
            _UNDO_COINS.inc(restored, what="restored")
            _UNDO_COINS.inc(removed, what="removed")
        sp.attrs.update(result=reason, restored=restored, removed=removed)
    return res


def _disconnect_block_impl(block, coins, undo, height, phase):
    """`disconnect_block` on a Python `CoinsView`: the native path's
    semantics (native/block.hpp view_disconnect_block), step for step."""
    txs = block.vtx
    with phase("undo_check"):
        if len(undo.spent) != len(txs):
            return "failed", 0, 0
        for tx, gone in zip(txs, undo.spent):
            want = [] if tx.is_coinbase() else [
                (i.prevout.hash, i.prevout.n) for i in tx.vin]
            if [key for key, _ in gone] != want:
                return "failed", 0, 0
    with phase("undo"):
        steps: list = []  # (key, the coin taken out, or None for one put in)

        def take_back():
            for key, coin in reversed(steps):
                if coin is None:
                    del coins._map[key]
                else:
                    coins._map[key] = coin
            return "unclean", 0, 0

        restored = removed = 0
        for tx, gone in zip(reversed(txs), reversed(undo.spent)):
            cb = tx.is_coinbase()
            for n, out in enumerate(tx.vout):
                key = (tx.txid, n)
                coin = coins._map.get(key)
                if (coin is None or coin.out.value != out.value
                        or coin.out.script_pubkey != out.script_pubkey
                        or coin.height != height or coin.coinbase != cb):
                    return take_back()
                steps.append((key, coins._map.pop(key)))
                removed += 1
            for key, coin in reversed(gone):
                if key in coins._map:
                    return take_back()
                coins._map[key] = coin
                steps.append((key, None))
                restored += 1
    return "ok", restored, removed


def overlay_tx_outputs(
    overlay: Dict[Tuple[bytes, int], Coin], tx: Tx, height: int
) -> None:
    """Record a tx's outputs in the in-block overlay so later txs of the
    same block can spend them (Core applies UpdateCoins per tx in order)."""
    cb = tx.is_coinbase()
    for n, out in enumerate(tx.vout):
        overlay[(tx.txid, n)] = Coin(out, height, cb)
