"""Persistent sigstore tests: replay fidelity, corruption fail-closed,
crash recovery, audit eviction of poisoned persisted entries.

The store's whole claim is that a restart warms from disk *without*
weakening any cache invariant: every corruption class (flipped
checksum byte, torn tail, kill -9 mid-append) must cost at most cache
misses — never a wrong hit, never a crash at open — and a poisoned
entry that made it to disk must be caught by the existing audit
re-verify and stay evicted across the NEXT restart (tombstone record).
"""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_LIBCONSENSUS
from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
from bitcoinconsensus_tpu.models.sigstore import (
    PersistentSigCache,
    _REC_LEN,
)
from bitcoinconsensus_tpu.resilience import guards
from bitcoinconsensus_tpu.resilience.faults import (
    FaultPlan,
    FaultSpec,
    inject,
)

from test_batch import make_p2wpkh_spend

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls


def _keys(n, seed=0):
    """n distinct 32-byte keys spread over shard bytes."""
    return [
        bytes([(seed + i) % 256]) + (seed + i).to_bytes(31, "little")
        for i in range(n)
    ]


def _store(tmp_path, **kw):
    kw.setdefault("hot_entries", 8)
    kw.setdefault("shards", 4)
    return PersistentSigCache(str(tmp_path / "store"), **kw)


def _one_log(tmp_path):
    d = tmp_path / "store"
    logs = sorted(
        p for p in os.listdir(d)
        if p.endswith(".log") and os.path.getsize(d / p) > 0
    )
    assert logs
    return d / logs[0]


# -- replay fidelity ---------------------------------------------------


def test_restart_replays_entries_and_salt(tmp_path):
    ks = _keys(20)
    s = _store(tmp_path)
    for k in ks:
        s.add_key(k)
    salt = s._salt
    s.close()

    s2 = _store(tmp_path)
    assert s2._salt == salt  # digests stay addressable across restarts
    assert len(s2) == 20
    assert s2.replay_applied == 20 and s2.replay_skipped == 0
    assert all(s2.contains_key(k) for k in ks)
    # 20 consecutive hits on a fresh instance: warm-up latched.
    assert s2.warmup_s is not None and s2.warmup_s >= 0
    s2.close()


def test_discard_tombstone_survives_restart(tmp_path):
    ks = _keys(6)
    s = _store(tmp_path)
    for k in ks:
        s.add_key(k)
    s.discard_key(ks[0])
    s.close()

    s2 = _store(tmp_path)
    assert len(s2) == 5
    assert not s2.contains_key(ks[0])
    assert all(s2.contains_key(k) for k in ks[1:])
    s2.close()


def test_hot_tier_overflow_never_loses_entries(tmp_path):
    """Hot-LRU eviction only demotes recency: every key stays servable
    from the disk tier (a cold hit that re-promotes)."""
    ks = _keys(50)
    s = _store(tmp_path, hot_entries=4)
    for k in ks:
        s.add_key(k)
    assert len(s) == 50
    assert all(s.contains_key(k) for k in ks)
    assert s.insertions - s.evictions - s.erases == len(s)
    s.close()


def test_bulk_forms_take_the_store_path(tmp_path):
    """`add_keys`/`contains_keys` (what the index-mode drivers call) go
    key by key through the store's own insert and probe: journalled,
    served from the disk tier past the hot bound, erase-on-hit durable."""
    ks = _keys(20)
    blob = b"".join(ks)
    s = _store(tmp_path, hot_entries=4)
    s.add_keys(blob, [i % 2 == 0 for i in range(20)])
    assert len(s) == 10 and s.insertions == 10
    s.close()

    s2 = _store(tmp_path, hot_entries=4)
    assert s2.replay_applied == 10
    assert s2.contains_keys(blob, 20).tolist() == [i % 2 == 0 for i in range(20)]
    assert (s2.hits, s2.misses) == (10, 10)
    assert s2.contains_keys(blob[:64], 2, erase=True).tolist() == [True, False]
    s2.close()
    s3 = _store(tmp_path, hot_entries=4)
    assert len(s3) == 9 and not s3.contains_key(ks[0])
    s3.close()


def test_erase_on_hit_persists(tmp_path):
    ks = _keys(4)
    s = _store(tmp_path)
    for k in ks:
        s.add_key(k)
    assert s.contains_key(ks[1], erase=True)
    assert not s.contains_key(ks[1])
    s.close()
    s2 = _store(tmp_path)
    assert not s2.contains_key(ks[1])
    assert len(s2) == 3
    s2.close()


def test_compaction_bounds_log_growth(tmp_path):
    """Repeated add/discard churn on one shard must trigger the
    compaction rewrite; the compacted log replays to the same live set."""
    s = _store(tmp_path, shards=1)
    churn = _keys(40, seed=7)
    keep = _keys(5, seed=200)
    for k in keep:
        s.add_key(k)
    for _ in range(4):
        for k in churn:
            s.add_key(k)
        for k in churn:
            s.discard_key(k)
    log = tmp_path / "store" / "shard-00.log"
    records = os.path.getsize(log) // _REC_LEN
    # Without compaction the churn alone wrote 4*80 = 320 records.
    assert records < 320
    assert records <= 2 * len(s) + 64 + 1
    s.close()
    s2 = _store(tmp_path, shards=1)
    assert len(s2) == 5
    assert all(s2.contains_key(k) for k in keep)
    assert not any(s2.contains_key(k) for k in churn)
    s2.close()


# -- corruption: fail-closed replay ------------------------------------


def test_flipped_checksum_byte_skips_record(tmp_path):
    ks = _keys(12)
    s = _store(tmp_path)
    for k in ks:
        s.add_key(k)
    s.close()

    log = _one_log(tmp_path)
    raw = bytearray(open(log, "rb").read())
    raw[len(raw) - 1] ^= 0xFF  # corrupt the last record's checksum
    open(log, "wb").write(bytes(raw))

    s2 = _store(tmp_path)
    assert s2.replay_skipped >= 1
    assert len(s2) < 12  # the corrupt record did NOT become an entry
    # Fail-closed means misses, not wrong hits: every surviving probe
    # answers from an intact record.
    assert s2.replay_applied + 12 - len(s2) >= 12 - 1
    # The log was truncated back to its last good record boundary.
    assert os.path.getsize(log) % _REC_LEN == 0
    assert os.path.getsize(log) == len(raw) - _REC_LEN
    s2.close()


def test_truncated_tail_record_skipped_and_healed(tmp_path):
    ks = _keys(10)
    s = _store(tmp_path)
    for k in ks:
        s.add_key(k)
    s.close()

    log = _one_log(tmp_path)
    good = os.path.getsize(log)
    with open(log, "ab") as fh:
        fh.write(b"\x41\x99\x07")  # torn append: 3 bytes of a record

    s2 = _store(tmp_path)
    assert s2.replay_skipped >= 1
    assert len(s2) == 10  # every intact record still replays
    assert os.path.getsize(log) == good  # healed back to the boundary
    # A subsequent append lands on the clean boundary and survives.
    extra = _keys(1, seed=99)[0]
    s2.add_key(extra)
    s2.close()
    s3 = _store(tmp_path)
    assert s3.contains_key(extra)
    s3.close()


def test_kill9_mid_append_recovers(tmp_path):
    """SIGKILL a writer process mid-append-loop; the survivor store must
    open cleanly: a whole-record prefix replays, any torn tail is
    skipped and healed, and the store keeps accepting writes."""
    store = str(tmp_path / "store")
    code = (
        "import sys\n"
        "sys.path.insert(0, %r)\n"
        "from bitcoinconsensus_tpu.models.sigstore import PersistentSigCache\n"
        "s = PersistentSigCache(%r, hot_entries=8, shards=4)\n"
        "print('ready', flush=True)\n"
        "i = 0\n"
        "while True:\n"
        "    i += 1\n"
        "    s.add_key(bytes([i %% 256]) + i.to_bytes(31, 'little'))\n"
    ) % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))), store)
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
    )
    try:
        assert proc.stdout.readline().strip() == b"ready"
        time.sleep(0.3)  # let the append loop run hot
        proc.send_signal(signal.SIGKILL)
        proc.wait(30)
    finally:
        if proc.poll() is None:  # pragma: no cover - cleanup
            proc.kill()

    s = PersistentSigCache(store, hot_entries=8, shards=4)
    assert len(s) > 0  # the flushed prefix survived the kill
    assert s.replay_applied == len(s)
    for p in os.listdir(store):
        if p.endswith(".log"):
            assert os.path.getsize(os.path.join(store, p)) % _REC_LEN == 0
    k = b"\xee" * 32
    s.add_key(k)
    s.close()
    s2 = PersistentSigCache(store, hot_entries=8, shards=4)
    assert s2.contains_key(k)
    s2.close()


# -- poisoned persisted entry: audit eviction --------------------------


def test_poisoned_persisted_entry_caught_by_audit(tmp_path):
    """Plant the key of a cryptographically-FALSE check in the store
    (what an undetected corruption or a hostile writer would amount
    to), restart, and verify under audit mode: the fabricated hit must
    be re-verified on host, rejected, and tombstoned — on disk too."""
    txb, spk, amt = make_p2wpkh_spend("sigstore-poison", corrupt=True)
    bad = BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                    spent_output_script=spk, amount=amt)
    txb2, spk2, amt2 = make_p2wpkh_spend("sigstore-clean")
    good = BatchItem(txb2, 0, VERIFY_ALL_LIBCONSENSUS,
                     spent_output_script=spk2, amount=amt2)

    s = _store(tmp_path)
    # Harvest the bad item's real cache keys (failure is never cached by
    # the driver, so a poisoned store is the only way they get in):
    # record its curve checks with the deferring checker, then plant
    # their digests by hand.
    res = verify_batch([bad, good], sig_cache=s)
    assert not res[0].ok and res[1].ok
    from bitcoinconsensus_tpu.core.interpreter import verify_script
    from bitcoinconsensus_tpu.core.sighash import PrecomputedTxData
    from bitcoinconsensus_tpu.core.tx import Tx
    from bitcoinconsensus_tpu.models.batch import DeferringSignatureChecker

    tx = Tx.deserialize(txb)
    checker = DeferringSignatureChecker(
        tx, 0, amt, PrecomputedTxData(tx), known={}
    )
    verify_script(
        tx.vin[0].script_sig, spk, tx.vin[0].witness,
        VERIFY_ALL_LIBCONSENSUS, checker,
    )
    poison_keys = s.keys_for_checks(checker.recorded)
    assert poison_keys
    for k in poison_keys:
        s.add_key(k)
    s.flush()
    del s  # crash, not close: the appended records were flushed

    s2 = _store(tmp_path)
    assert all(s2.contains_key(k) for k in poison_keys)  # poison warm
    before = guards.CACHE_POISON_CAUGHT.value(cache="sig")
    guards.set_cache_audit(True)
    try:
        res2 = verify_batch([bad, good], sig_cache=s2)
    finally:
        guards.set_cache_audit(False)
    # Audit caught the fabricated hit: verdict right, entry evicted.
    assert not res2[0].ok and res2[1].ok
    assert guards.CACHE_POISON_CAUGHT.value(cache="sig") > before
    assert not any(s2.contains_key(k) for k in poison_keys)
    s2.close()
    # The eviction is durable: a THIRD process start stays clean.
    s3 = _store(tmp_path)
    assert not any(s3.contains_key(k) for k in poison_keys)
    s3.close()


# -- fault sites -------------------------------------------------------


def test_load_fault_leaves_shard_cold(tmp_path):
    ks = _keys(16)
    s = _store(tmp_path)
    for k in ks:
        s.add_key(k)
    s.close()
    plan = FaultPlan([FaultSpec(site="sigstore.load", kind="raise", count=1)])
    with inject(plan, seed=3) as inj:
        s2 = _store(tmp_path)
    assert inj.fired[("sigstore.load", "raise")] == 1
    # One shard started cold (contained), the rest replayed.
    assert 0 < len(s2) < 16
    assert s2.replay_skipped >= 1
    s2.close()


def test_append_fault_costs_persistence_not_correctness(tmp_path):
    s = _store(tmp_path)
    k_lost, k_kept = _keys(2, seed=50)
    plan = FaultPlan(
        [FaultSpec(site="sigstore.append", kind="raise", count=1)]
    )
    with inject(plan, seed=3) as inj:
        s.add_key(k_lost)  # append fails: in-RAM only
    assert inj.fired[("sigstore.append", "raise")] == 1
    s.add_key(k_kept)
    assert s.contains_key(k_lost) and s.contains_key(k_kept)  # RAM fine
    s.close()
    s2 = _store(tmp_path)
    assert not s2.contains_key(k_lost)  # the one unpersisted entry
    assert s2.contains_key(k_kept)
    s2.close()


# -- concurrency -------------------------------------------------------


def test_concurrent_hammer_preserves_accounting_invariant(tmp_path):
    """The sigcache S2 hammer, on the persistent store: racing insert /
    erase-on-hit / probe / discard threads must close the accounting
    (insertions - evictions - erases == live entries), and a restart
    must replay to exactly the surviving live set."""
    s = _store(tmp_path, hot_entries=16, shards=4)
    n_threads, n_ops = 8, 200
    barrier = threading.Barrier(n_threads)
    errors = []

    def worker(tid):
        try:
            barrier.wait()
            for i in range(n_ops):
                k = (
                    bytes([(i * 13 + tid) % 97])
                    + ((i % 31) * 1000 + tid % 3).to_bytes(31, "little")
                )
                op = (tid + i) % 4
                if op == 0:
                    s.add_key(k)
                elif op == 1:
                    s.contains_key(k, erase=True)
                elif op == 2:
                    s.contains_key(k)
                else:
                    s.discard_key(k)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors
    assert not any(t.is_alive() for t in threads)
    assert s.insertions - s.evictions - s.erases == len(s)
    live = {
        k
        for shard in s._cold
        for k in shard
    }
    assert len(live) == len(s)
    s.close()
    # Restart replays exactly the surviving set (adds/discards raced in
    # RAM and on disk in the SAME order — the store lock spans both).
    s2 = _store(tmp_path, hot_entries=16, shards=4)
    assert len(s2) == len(live)
    assert all(s2.contains_key(k) for k in live)
    s2.close()
