"""Cross-batch caches: repeat batches must skip the device; failures must
never be cached; keys must commit to the spent outputs.

Reference contract: `script/sigcache.cpp:22-122` (salted, success-only)
and `validation.cpp:1529-1536` (script cache keyed on wtxid+flags)."""

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_LIBCONSENSUS
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier, default_verifier
from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from test_batch import make_p2wpkh_spend

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls


class CountingVerifier(TpuSecpVerifier):
    """Counts lanes actually dispatched; shares the process jit cache."""

    def __init__(self):
        super().__init__()
        self.dispatched = 0

    def verify_checks(self, checks):
        self.dispatched += len(checks)
        return default_verifier().verify_checks(checks)

    def dispatch_lanes(self, args, n):  # the index-mode driver's seam
        self.dispatched += n
        return super().dispatch_lanes(args, n)


def _items(seeds, corrupt=()):
    items = []
    for s in seeds:
        txb, spk, amt = make_p2wpkh_spend(s, corrupt=s in corrupt)
        items.append(
            BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS, spent_output_script=spk, amount=amt)
        )
    return items


def test_repeat_batch_skips_device_entirely():
    v = CountingVerifier()
    sig, script = SigCache(), ScriptExecutionCache()
    items = _items(["c1", "c2", "c3"])
    res1 = verify_batch(items, verifier=v, sig_cache=sig, script_cache=script)
    assert all(r.ok for r in res1)
    first = v.dispatched
    assert first == 3
    # Same batch again: script-cache hits -> no interpretation, no device.
    res2 = verify_batch(items, verifier=v, sig_cache=sig, script_cache=script)
    assert all(r.ok for r in res2)
    assert v.dispatched == first
    assert script.hits >= 3


def test_sig_cache_alone_skips_dispatch():
    v = CountingVerifier()
    sig = SigCache()
    items = _items(["s1", "s2"])
    verify_batch(items, verifier=v, sig_cache=sig, script_cache=ScriptExecutionCache())
    assert v.dispatched == 2
    # Fresh script cache: interpretation re-runs, but every curve check is
    # sig-cache-known -> zero device lanes.
    verify_batch(items, verifier=v, sig_cache=sig, script_cache=ScriptExecutionCache())
    assert v.dispatched == 2
    assert sig.hits >= 2


def test_failures_never_cached():
    v = CountingVerifier()
    sig, script = SigCache(), ScriptExecutionCache()
    items = _items(["f1"], corrupt={"f1"})
    r1 = verify_batch(items, verifier=v, sig_cache=sig, script_cache=script)
    assert not r1[0].ok
    d1 = v.dispatched
    r2 = verify_batch(items, verifier=v, sig_cache=sig, script_cache=script)
    assert not r2[0].ok
    assert v.dispatched > d1  # re-dispatched: failure was not cached
    assert len(sig) == 0 and len(script) == 0


def test_script_cache_key_commits_to_spent_outputs():
    txb, spk, amt = make_p2wpkh_spend("k1")
    good = BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS, spent_output_script=spk, amount=amt)
    # Same tx, wrong amount: BIP143 sighash differs -> invalid.
    bad = BatchItem(
        txb, 0, VERIFY_ALL_LIBCONSENSUS, spent_output_script=spk, amount=amt + 1
    )
    sig, script = SigCache(), ScriptExecutionCache()
    v = CountingVerifier()
    assert verify_batch([good], verifier=v, sig_cache=sig, script_cache=script)[0].ok
    # The cached success for `good` must NOT leak to `bad`.
    assert not verify_batch([bad], verifier=v, sig_cache=sig, script_cache=script)[0].ok


def test_lru_bound():
    sig = SigCache(max_entries=4)
    for i in range(10):
        sig.add_check("ecdsa", (b"pk%d" % i, b"sig", b"m"))
    assert len(sig) == 4
    assert sig.contains_check("ecdsa", (b"pk9", b"sig", b"m"))
    assert not sig.contains_check("ecdsa", (b"pk0", b"sig", b"m"))


def test_registry_metrics_mirror_cache_counters():
    """The labeled registry children must track the legacy attrs exactly,
    and the documented invariants must hold: hits + misses == lookups and
    insertions - evictions - erases == len(cache)."""
    import os

    from bitcoinconsensus_tpu.obs import get_registry

    label = "invtest-" + os.urandom(4).hex()  # isolate registry children
    reg = get_registry()

    def m(name):
        metric = reg.get(f"consensus_cache_{name}")
        return metric.value(cache=label)

    sig = SigCache(max_entries=4, cache_label=label)
    for i in range(10):
        sig.add_check("ecdsa", (b"pk%d" % i, b"sig", b"m"))
    assert m("insertions_total") == sig.insertions == 10
    assert m("evictions_total") == sig.evictions == 6
    assert m("entries") == len(sig) == 4

    for i in range(10):
        hit = sig.contains_check("ecdsa", (b"pk%d" % i, b"sig", b"m"))
        assert hit == (i >= 6)  # pk6..pk9 survived the LRU bound
    assert m("lookups_total") == 10
    assert m("hits_total") == sig.hits == 4
    assert m("misses_total") == sig.misses == 6
    assert m("hits_total") + m("misses_total") == m("lookups_total")

    # erase-on-hit (Core's mempool->block pattern) removes and counts.
    assert sig.contains_check("ecdsa", (b"pk9", b"sig", b"m"), erase=True)
    assert not sig.contains_check("ecdsa", (b"pk9", b"sig", b"m"))
    assert m("erases_total") == sig.erases == 1
    assert m("entries") == len(sig) == 3
    assert (
        m("insertions_total") - m("evictions_total") - m("erases_total")
        == len(sig)
    )


def test_concurrent_hammer_preserves_accounting_invariant():
    """Threads racing insert / erase-on-hit / discard on a small LRU:
    whatever interleaving happens, the byte-for-byte accounting must
    close — insertions - evictions - erases == live entries. A hole here
    means a lost ticket: an entry (or its counter) dropped on a race,
    exactly the failure mode the serving layer's shared caches would
    amplify under concurrent tenants."""
    import threading

    sig = SigCache(max_entries=64, cache_label="hammer")
    n_threads, n_ops = 8, 400
    barrier = threading.Barrier(n_threads)
    errors = []

    def worker(tid):
        try:
            barrier.wait()
            for i in range(n_ops):
                data = (b"pk%d" % (i % 97), b"sig%d" % (tid % 3), b"m")
                op = (tid + i) % 4
                if op == 0:
                    sig.add_check("ecdsa", data)
                elif op == 1:
                    sig.contains_check("ecdsa", data, erase=True)
                elif op == 2:
                    sig.contains_check("ecdsa", data)
                else:
                    sig.discard_key(sig._key(sig._parts("ecdsa", data)))
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
    assert sig.insertions - sig.evictions - sig.erases == len(sig)
    assert 0 <= len(sig) <= 64
    # The cache still functions after the stampede.
    sig.add_check("ecdsa", (b"post", b"hammer", b"m"))
    assert sig.contains_check("ecdsa", (b"post", b"hammer", b"m"))
    assert sig.insertions - sig.evictions - sig.erases == len(sig)
