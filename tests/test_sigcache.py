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
    """Threads racing insert / erase-on-hit / discard, one key at a time
    and in bulk, on a small LRU:
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
                op = (tid + i) % 6
                if op == 0:
                    sig.add_check("ecdsa", data)
                elif op == 1:
                    sig.contains_check("ecdsa", data, erase=True)
                elif op == 2:
                    sig.contains_check("ecdsa", data)
                elif op == 3:
                    sig.discard_key(sig._key(sig._parts("ecdsa", data)))
                else:  # the bulk forms race the single-key ones
                    blob = b"".join(
                        sig._key(sig._parts("ecdsa", (b"pk%d" % ((i + d) % 97),)
                                            + data[1:]))
                        for d in range(5)
                    )
                    if op == 4:
                        sig.add_keys(blob, [True, False, True, True, False])
                    else:
                        sig.contains_keys(blob, 5, erase=bool(i & 1))
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


# -- bulk probe / insert vs the single-key methods ----------------------


def _k(i):
    return bytes([i]) * 32


def _blob(ids):
    return b"".join(_k(i) for i in ids)


# One scenario = cache bound, fault site armed or not, and steps run in
# order: ("add", key ids, select) with select None / a bool mask / an index
# list over the ids, or ("probe", key ids, erase).
_BULK_SCENARIOS = {
    "cold": (64, False, [("probe", range(8), False)]),
    "all_hits": (64, False, [("add", range(8), None),
                             ("probe", range(8), False)]),
    "mixed": (64, False, [
        ("add", range(10), [i % 2 == 0 for i in range(10)]),  # a mask
        ("probe", range(10), False),
        ("add", range(10), [9, 3, 4]),  # indices, in the order given
        ("probe", [3, 11, 9, 0], False),
    ]),
    "duplicates": (64, False, [
        ("add", [1, 2, 1, 3, 2], None),
        ("probe", [1, 1, 4, 2, 2, 4], False),
    ]),
    "eviction": (4, False, [
        ("add", [0, 1, 2], None),
        # crosses max_entries inside one call; 1 is touched before it
        # would be evicted, 0 is evicted and re-inserted
        ("add", [3, 1, 4, 5, 0, 6, 7, 8], None),
        ("probe", range(9), False),
    ]),
    "erase": (64, False, [
        ("add", range(6), None),
        ("probe", [0, 1, 0, 9, 5], True),  # the second 0 is a miss
        ("probe", range(6), False),
    ]),
    "n0": (64, False, [("probe", [], False), ("add", [], None),
                       ("add", range(3), [False] * 3)]),
    "n1": (64, False, [("probe", [7], False), ("add", [7], None),
                       ("probe", [7], False), ("probe", [7], True)]),
    "poison": (64, True, [("add", range(4), None),
                          ("probe", [9, 0, 8, 1, 7], False)]),
}


@pytest.mark.parametrize("name", list(_BULK_SCENARIOS))
def test_bulk_methods_match_single_key_methods(name, monkeypatch):
    """`contains_keys`/`add_keys` leave a cache exactly where the same
    keys through `contains_key`/`add_key` leave its twin: answers, LRU
    order, the counters on the object and in the registry. With a poison
    plan armed the bulk probe walks the per-key path, so the fabricated
    hit lands on the same visit and is counted."""
    import os

    import numpy as np

    from bitcoinconsensus_tpu.obs import get_registry
    from bitcoinconsensus_tpu.resilience.faults import (
        FaultPlan,
        FaultSpec,
        inject,
    )

    max_entries, poisoned, steps = _BULK_SCENARIOS[name]
    tag = os.urandom(4).hex()
    bulk = SigCache(max_entries, cache_label=f"bulk-{name}-{tag}")
    single = SigCache(max_entries, cache_label=f"single-{name}-{tag}")
    per_key_probes = []
    real = SigCache.contains_key
    monkeypatch.setattr(
        SigCache, "contains_key",
        lambda self, k, erase=False: (
            per_key_probes.append(self) or real(self, k, erase)
        ),
    )

    def run(cache, use_bulk):
        answers = []
        for step in steps:
            ids = list(step[1])
            if step[0] == "add":
                sel = step[2]
                if use_bulk:
                    cache.add_keys(
                        _blob(ids), None if sel is None else np.asarray(sel)
                    )
                    continue
                if sel is None:
                    picked = ids
                elif sel and isinstance(sel[0], bool):
                    picked = [i for i, on in zip(ids, sel) if on]
                else:
                    picked = [ids[j] for j in sel]
                for i in picked:
                    cache.add_key(_k(i))
            elif use_bulk:
                got = cache.contains_keys(_blob(ids), len(ids), erase=step[2])
                assert got.dtype == bool and got.shape == (len(ids),)
                answers.append(got.tolist())
            else:
                answers.append(
                    [cache.contains_key(_k(i), erase=step[2]) for i in ids]
                )
        return answers

    def armed(cache):
        # the site's first three probes report a hit, present or not
        site = cache._poison_site
        return inject(FaultPlan([FaultSpec(site, "poison", count=3)]))

    if poisoned:
        with armed(bulk) as inj_b:
            got = run(bulk, True)
        with armed(single) as inj_s:
            want = run(single, False)
        assert inj_b.total_fired() == inj_s.total_fired() == 3
        # ids 9 and 8 are absent: both were fabricated hits, and counted
        assert got == [[True, True, True, True, False]]
        assert per_key_probes.count(bulk) == 5  # the per-key path
    else:
        got, want = run(bulk, True), run(single, False)
        assert per_key_probes.count(bulk) == 0  # one lock hold, no fan-out
    assert got == want
    assert list(bulk._set) == list(single._set)  # LRU order, oldest first
    for attr in ("hits", "misses", "insertions", "evictions", "erases"):
        assert getattr(bulk, attr) == getattr(single, attr), attr
    assert bulk.hits + bulk.misses == sum(len(a) for a in got)
    assert bulk.insertions - bulk.evictions - bulk.erases == len(bulk)
    reg = get_registry()
    for metric in ("lookups_total", "hits_total", "misses_total",
                   "insertions_total", "evictions_total", "erases_total",
                   "entries"):
        m = reg.get(f"consensus_cache_{metric}")
        assert m.value(cache=bulk._poison_site[9:]) == m.value(
            cache=single._poison_site[9:]
        ), metric
    if name == "eviction":
        assert bulk.evictions == 6 and list(bulk._set) == [_k(i) for i in (0, 6, 7, 8)]
    if name == "erase":
        assert bulk.erases == 3 and got[0] == [True, True, False, False, True]
