"""Cross-batch signature & script-execution caches.

Production Bitcoin Core skips re-verification of signatures it already
checked at mempool acceptance when the same tx appears in a block: a
salted-SHA256-keyed cuckoo set for (sighash, pubkey, sig) triples
(`script/sigcache.cpp:22-122`) and a second one for whole-tx script
success keyed on wtxid+flags (`validation.cpp:1477-1495,1529-1536`). Both
store *successes only* — failure is never cached, so a cache bug can only
cost work, not consensus.

TPU-era equivalents, same contract:

- `SigCache`: batch-dispatch front-end — hits resolve without shipping the
  lane to the device; verified-true lanes are inserted after each
  dispatch.
- `ScriptExecutionCache`: per-(wtxid, input, flags, spent-outputs) script
  success, probed before interpretation. The spent-outputs digest is part
  of the key because our API (unlike Core's UTXO view) lets callers
  supply arbitrary prevouts for the same tx.

Keys are salted per process (`os.urandom`) exactly as the reference salts
its hashers (sigcache.cpp:22-30) — entries are never addressable across
processes, so a poisoned entry cannot be constructed offline. Storage is
a bounded LRU set rather than a cuckoo table: the reference's cuckoo
design buys lock-free concurrent probes on 32 B entries; one mutex a cache
has the same asymptotics with far less machinery. Where the native core
is loaded the keys live in its LRU set (`native/lru.hpp`): a call, bulk or
single, is ONE C call that takes the set's mutex once and walks its keys
with the GIL released, so a block's probes and inserts cost the memory's
time and two threads run side by side up to that lock. Otherwise, and in
`PersistentSigCache`, they live in an OrderedDict behind a lock: the same
contract key for key, and the reference `tests/test_sigcache.py` holds the
native set to. Nothing but `native_bridge.available()` and the class
chooses. All methods hold the mutex, making concurrent `verify_batch` calls
safe — the thread contract the reference documents for its own globals
(`pubkey.h:257-258`) and SURVEY §5 requires of ours.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from typing import Iterable, Optional, Tuple

import numpy as np

from .. import native_bridge
from ..obs import counter as _obs_counter
from ..obs import gauge as _obs_gauge
from ..resilience import faults as _faults

__all__ = [
    "SigCache",
    "ScriptExecutionCache",
    "default_sig_cache",
    "default_script_cache",
]

# Cache telemetry, labeled by cache role ("sig" / "script"; tests pass
# their own labels to isolate). Invariants asserted by tests/test_sigcache:
# hits + misses == lookups; insertions - evictions - erases == entries.
_C_LOOKUPS = _obs_counter(
    "consensus_cache_lookups_total", "cache probes", ("cache",)
)
_C_HITS = _obs_counter("consensus_cache_hits_total", "cache hits", ("cache",))
_C_MISSES = _obs_counter(
    "consensus_cache_misses_total", "cache misses", ("cache",)
)
_C_INSERTS = _obs_counter(
    "consensus_cache_insertions_total", "cache insertions", ("cache",)
)
_C_EVICTS = _obs_counter(
    "consensus_cache_evictions_total", "LRU evictions past max_entries",
    ("cache",),
)
_C_ERASES = _obs_counter(
    "consensus_cache_erases_total",
    "erase-on-hit removals (Core's mempool->block pattern)", ("cache",),
)
_C_ENTRIES = _obs_gauge(
    "consensus_cache_entries", "current cache entry count", ("cache",)
)


_C_BULK = _obs_counter(
    "consensus_cache_bulk_keys_total",
    "keys walked by bulk probes and inserts, by where the set lives "
    "(native: one C call under one lock hold; python: a loop a key)",
    ("cache", "store"),
)


def _counter(name: str) -> property:
    """One of the five counters: the native set's own, which moves under the
    set's mutex, where the keys live there; else a plain attribute, written
    under `_lock`."""
    slot = "_" + name

    def read(self) -> int:
        if self._nat is not None:
            return self._nat.counters()[name]
        return self.__dict__[slot]

    def write(self, value: int) -> None:
        self.__dict__[slot] = value

    return property(read, write)


class _SaltedLRU:
    """Bounded success-set with a per-process salted key digest."""

    # A subclass that reads and writes `_set` as a dict and journals a key at
    # a time (models/sigstore.py) keeps the Python set, native core or not.
    _python_set = False

    hits = _counter("hits")
    misses = _counter("misses")
    insertions = _counter("insertions")
    evictions = _counter("evictions")
    erases = _counter("erases")

    def __init__(self, max_entries: int, cache_label: str = "cache"):
        assert max_entries > 0
        self._salt = os.urandom(32)
        self._max = max_entries
        # Chaos-harness injection site (resilience/faults.py): an armed
        # "poison" fault makes one probe report a fabricated hit, the
        # observable a genuinely poisoned entry would produce.
        self._poison_site = "sigcache." + cache_label
        if self._python_set or not native_bridge.available():
            self._nat = None
            self._set: OrderedDict[bytes, None] = OrderedDict()
            self._lock = threading.Lock()
            self.hits = 0
            self.misses = 0
            self.evictions = 0
            self.erases = 0
            self.insertions = 0
        else:
            self._nat = native_bridge.NativeLruSet(max_entries)
        # Bound metric children: one dict lookup + label-key build at
        # construction, plain locked adds on the probe/insert hot paths.
        lbl = {"cache": cache_label}
        self._m_bulk = _C_BULK.labels(
            store="python" if self._nat is None else "native", **lbl
        )
        self._m_lookups = _C_LOOKUPS.labels(**lbl)
        self._m_hits = _C_HITS.labels(**lbl)
        self._m_misses = _C_MISSES.labels(**lbl)
        self._m_inserts = _C_INSERTS.labels(**lbl)
        self._m_evicts = _C_EVICTS.labels(**lbl)
        self._m_erases = _C_ERASES.labels(**lbl)
        self._m_entries = _C_ENTRIES.labels(**lbl)

    def _key(self, parts: Iterable[bytes]) -> bytes:
        h = hashlib.sha256(self._salt)
        for p in parts:
            h.update(len(p).to_bytes(4, "little"))
            h.update(p)
        return h.digest()

    def contains_key(self, k: bytes, erase: bool = False) -> bool:
        """Probe by a precomputed digest (see SigCache.keys_for_checks)."""
        poisoned = _faults.poison_hit(self._poison_site)
        if self._nat is not None:
            present, size = self._nat.probe_one(k, erase, poisoned)
        else:
            with self._lock:
                present = k in self._set
                if present:
                    self.hits += 1
                    if erase:
                        del self._set[k]
                        self.erases += 1
                    else:
                        self._set.move_to_end(k)
                elif poisoned:
                    # Fabricated hit, set untouched: counted as a hit so the
                    # hits+misses==lookups invariant holds under chaos.
                    self.hits += 1
                else:
                    self.misses += 1
                size = len(self._set)
        hit = present or poisoned
        # Registry updates outside the cache lock: no nested-lock ordering
        # to reason about, and a slow metrics path can never stall probes.
        self._m_lookups.inc()
        if hit:
            self._m_hits.inc()
            if present and erase:
                self._m_erases.inc()
                self._m_entries.set(size)
        else:
            self._m_misses.inc()
        return hit

    def contains_keys(
        self, blob: bytes, n: int, erase: bool = False
    ) -> np.ndarray:
        """Probe the `n` digests packed in `blob` (32 bytes each); returns
        the hit mask. One lock hold, and inside it `contains_key`'s steps
        per key in blob order, so answers, LRU order and the counters end
        where `n` single probes would leave them; the registry is raised
        once, by the call's totals. With a fault plan armed the probes go
        one by one through `contains_key`: the poison site counts visits."""
        if _faults.active() is not None:
            return self._contains_each(blob, n, erase)
        if self._nat is not None:
            hit, hits, size = self._nat.probe(blob, n, erase)
        else:
            hit = [False] * n
            with self._lock:
                s = self._set
                touch = s.__delitem__ if erase else s.move_to_end
                for j in range(n):
                    k = blob[32 * j : 32 * j + 32]
                    if k in s:
                        touch(k)
                        hit[j] = True
                hits = hit.count(True)
                self.hits += hits
                self.misses += n - hits
                if erase:
                    self.erases += hits
                size = len(s)
            hit = np.array(hit, dtype=bool)
        if n:
            self._m_bulk.inc(n)
            self._m_lookups.inc(n)
        if hits:
            self._m_hits.inc(hits)
        if n - hits:
            self._m_misses.inc(n - hits)
        if erase and hits:
            self._m_erases.inc(hits)
            self._m_entries.set(size)
        return hit

    def discard_key(self, k: bytes) -> None:
        """Drop a proven-wrong entry (resilience cache-audit containment).

        No-op when absent. Counted as an erase so the entry-count
        invariant (insertions - evictions - erases == entries) holds."""
        if self._nat is not None:
            present, size = self._nat.discard(k)
        else:
            with self._lock:
                present = k in self._set
                if present:
                    del self._set[k]
                    self.erases += 1
                size = len(self._set)
        if present:
            self._m_erases.inc()
            self._m_entries.set(size)

    def add_key(self, k: bytes) -> None:
        # A re-add of a present key is a freshness touch, not an insertion:
        # counting it would break the entry-accounting invariant
        # (insertions - evictions - erases == entries) that concurrent
        # writers rely on to detect lost entries.
        if self._nat is not None:
            new, evicted, size = self._nat.add_one(k)
        else:
            with self._lock:
                new = k not in self._set
                self._set[k] = None
                self._set.move_to_end(k)
                evicted = 0
                while len(self._set) > self._max:
                    self._set.popitem(last=False)
                    evicted += 1
                self.evictions += evicted
                if new:
                    self.insertions += 1
                size = len(self._set)
        if new:
            self._m_inserts.inc()
        if evicted:
            self._m_evicts.inc(evicted)
        self._m_entries.set(size)

    def add_keys(self, blob: bytes, select=None) -> None:
        """Insert digests of `blob` (32 bytes each): those a bool mask
        marks, in ascending order; those an index array names, in its
        order; all of them when `select` is None. One lock hold, `add_key`'s
        steps per key inside it, the registry raised once by the totals."""
        idx = self._selected(blob, select)
        if not len(idx):
            return
        if self._nat is not None:
            inserted, evicted, size = self._nat.add(blob, idx)
        else:
            inserted = evicted = 0
            with self._lock:
                s = self._set
                for j in idx.tolist():
                    k = blob[32 * j : 32 * j + 32]
                    if k in s:  # a freshness touch, not an insertion
                        s.move_to_end(k)
                        continue
                    s[k] = None
                    inserted += 1
                    while len(s) > self._max:
                        s.popitem(last=False)
                        evicted += 1
                self.insertions += inserted
                self.evictions += evicted
                size = len(s)
        self._m_bulk.inc(len(idx))
        if inserted:
            self._m_inserts.inc(inserted)
        if evicted:
            self._m_evicts.inc(evicted)
        self._m_entries.set(size)

    @staticmethod
    def _selected(blob: bytes, select) -> np.ndarray:
        """The rows of `blob` that `select` names, in insertion order."""
        if select is None:
            return np.arange(len(blob) // 32, dtype=np.int64)
        sel = np.asarray(select)
        if sel.dtype == bool:
            return np.flatnonzero(sel)
        return np.ascontiguousarray(sel, dtype=np.int64)

    # The bulk forms as `n` single-key calls: what `contains_keys` does
    # under a fault plan, and what a subclass with a `contains_key` /
    # `add_key` of its own binds the bulk names to (models/sigstore.py).

    def _contains_each(
        self, blob: bytes, n: int, erase: bool = False
    ) -> np.ndarray:
        return np.fromiter(
            (self.contains_key(blob[32 * j : 32 * j + 32], erase)
             for j in range(n)),
            dtype=bool, count=n,
        )

    def _add_each(self, blob: bytes, select=None) -> None:
        for j in self._selected(blob, select).tolist():
            self.add_key(blob[32 * j : 32 * j + 32])

    def contains(self, parts: Iterable[bytes], erase: bool = False) -> bool:
        return self.contains_key(self._key(parts), erase=erase)

    def add(self, parts: Iterable[bytes]) -> None:
        self.add_key(self._key(parts))

    def keys_for_parts(self, items) -> list:
        """Digests for many part-tuples in one native call (byte-identical
        to `_key`; Python fallback otherwise). Pair with
        `contains_key`/`add_key` to amortize hashing over a batch."""
        if native_bridge.available():
            return native_bridge.digest_streams(self._salt, items)
        return [self._key(parts) for parts in items]

    def keys_oldest_first(self) -> list:
        """The keys in the order eviction would take them."""
        if self._nat is not None:
            return self._nat.keys_oldest_first()
        with self._lock:
            return list(self._set)

    def __len__(self) -> int:
        return len(self._set) if self._nat is None else len(self._nat)


class SigCache(_SaltedLRU):
    """Valid-signature set over deferred curve checks (sigcache.cpp:22-122).

    A `SigCheck`'s (kind, data) tuple is flattened into the salted digest;
    `contains` on a hit refreshes recency (Core's mempool->block pattern
    uses erase-on-hit from the block path; pass erase=True to match)."""

    def __init__(self, max_entries: int = 1 << 16, cache_label: str = "sig"):
        super().__init__(max_entries, cache_label=cache_label)

    @staticmethod
    def _parts(kind: str, data: Tuple) -> Tuple[bytes, ...]:
        # Ints serialize at 8 bytes signed so a future check kind carrying
        # e.g. a satoshi amount can never overflow the key builder (the
        # length-prefixed digest keeps 4- and 8-byte encodings distinct).
        parts = [kind.encode()]
        for d in data:
            parts.append(
                d if isinstance(d, bytes) else int(d).to_bytes(8, "little", signed=True)
            )
        return tuple(parts)

    def contains_check(self, kind: str, data: Tuple, erase: bool = False) -> bool:
        return self.contains(self._parts(kind, data), erase=erase)

    def add_check(self, kind: str, data: Tuple) -> None:
        self.add(self._parts(kind, data))

    def keys_for_checks(self, checks) -> list:
        """Digests for many SigCheck-shaped (kind, data) checks in one
        native call (byte-identical to `_key(_parts(...))`, asserted by
        tests/test_sigcache.py); Python fallback otherwise. Use with
        `contains_key`/`add_key` to amortize hashing over a batch."""
        pairs = [(c.kind, c.data) for c in checks]
        if native_bridge.available():
            return native_bridge.digest_checks(self._salt, pairs)
        return [self._key(self._parts(k, d)) for k, d in pairs]


class ScriptExecutionCache(_SaltedLRU):
    """Per-input script success keyed on (wtxid, input index, flags,
    spent-outputs digest) — validation.cpp:1529-1536 reshaped to the
    per-input batch API."""

    def __init__(self, max_entries: int = 1 << 15, cache_label: str = "script"):
        super().__init__(max_entries, cache_label=cache_label)

    @staticmethod
    def _parts(
        wtxid: bytes, n_in: int, flags: int, spent_digest: bytes
    ) -> Tuple[bytes, ...]:
        return (
            wtxid,
            n_in.to_bytes(4, "little"),
            flags.to_bytes(4, "little"),
            spent_digest,
        )

    @staticmethod
    def spent_digest(spent_outputs) -> bytes:
        """Digest of the (amount, scriptPubKey) list a caller supplied
        (empty-sentinel for the legacy single-prevout form)."""
        h = hashlib.sha256()
        if spent_outputs is None:
            return b"\x00" * 32
        for amt, spk in spent_outputs:
            h.update(int(amt).to_bytes(8, "little", signed=True))
            h.update(len(spk).to_bytes(4, "little"))
            h.update(spk)
        return h.digest()

    def contains_input(
        self, wtxid: bytes, n_in: int, flags: int, spent_digest: bytes
    ) -> bool:
        return self.contains(self._parts(wtxid, n_in, flags, spent_digest))

    def add_input(
        self, wtxid: bytes, n_in: int, flags: int, spent_digest: bytes
    ) -> None:
        self.add(self._parts(wtxid, n_in, flags, spent_digest))


_default_sig: Optional[SigCache] = None
_default_script: Optional[ScriptExecutionCache] = None
_default_lock = threading.Lock()


def default_sig_cache() -> SigCache:
    global _default_sig
    with _default_lock:
        if _default_sig is None:
            _default_sig = SigCache()
        return _default_sig


def default_script_cache() -> ScriptExecutionCache:
    global _default_script
    with _default_lock:
        if _default_script is None:
            _default_script = ScriptExecutionCache()
        return _default_script
