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
    assert bulk.keys_oldest_first() == single.keys_oldest_first()  # LRU order, oldest first
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
        assert bulk.evictions == 6 and bulk.keys_oldest_first() == [_k(i) for i in (0, 6, 7, 8)]
    if name == "erase":
        assert bulk.erases == 3 and got[0] == [True, True, False, False, True]


# -- the native set (native/lru.hpp) against the Python set ---------------

from bitcoinconsensus_tpu import native_bridge  # noqa: E402

needs_native = pytest.mark.skipif(
    not native_bridge.available(), reason="native core unavailable"
)
_COUNTERS = native_bridge.NativeLruSet.COUNTERS
_REGISTRY = ("lookups_total", "hits_total", "misses_total",
             "insertions_total", "evictions_total", "erases_total")


def _python_set_cache(monkeypatch, max_entries, label):
    """A `SigCache` made where the native core cannot be loaded: its keys
    live in the OrderedDict, the plain reference."""
    with monkeypatch.context() as mp:
        mp.setattr(native_bridge, "available", lambda: False)
        cache = SigCache(max_entries, cache_label=label)
    assert cache._nat is None
    return cache


def _registry(label, name, **more):
    from bitcoinconsensus_tpu.obs import get_registry

    return get_registry().get(f"consensus_cache_{name}").value(
        cache=label, **more)


@needs_native
@pytest.mark.parametrize("max_entries", [5, 24])
@pytest.mark.parametrize("seed", range(6))
def test_native_set_matches_python_set(seed, max_entries, monkeypatch):
    """Seeded random sequences of every operation on a native-set cache
    and on its Python-set twin, over a pool of keys a few times the bound,
    so that bulk calls evict in their middle and re-add what they evicted:
    after every step the answers, the five counters and the keys oldest
    first are equal; at the end so is every registry series, and each
    side's bulk keys were counted under its own store."""
    import os
    import random

    import numpy as np

    rng = random.Random(1000 * max_entries + seed)
    tag = os.urandom(4).hex()
    labels = f"nat-{tag}", f"py-{tag}"
    nat = SigCache(max_entries, cache_label=labels[0])
    py = _python_set_cache(monkeypatch, max_entries, labels[1])
    assert nat._nat is not None
    pool = [rng.randbytes(32) for _ in range(3 * max_entries)]
    bulk_keys = 0

    def step(cache):
        r = random.Random(step_seed)
        op = r.choice(("add_key", "add_keys", "add_keys_mask", "add_keys_idx",
                       "contains_key", "contains_keys", "discard_key"))
        ks = [r.choice(pool) for _ in range(r.randrange(0, 2 * max_entries))]
        blob, erase = b"".join(ks), r.random() < 0.4
        if op == "add_key":
            return op, 0, cache.add_key(r.choice(pool))
        if op == "add_keys":
            return op, len(ks), cache.add_keys(blob)
        if op == "add_keys_mask":
            mask = np.array([r.random() < 0.6 for _ in ks], dtype=bool)
            return op, int(mask.sum()), cache.add_keys(blob, mask)
        if op == "add_keys_idx":  # any order, with repeats, int32 as batch.py's
            idx = np.array([r.randrange(len(ks)) for _ in ks if r.random() < 0.7],
                           dtype=np.int32)
            return op, len(idx), cache.add_keys(blob, idx)
        if op == "contains_key":
            return op, 0, cache.contains_key(r.choice(pool), erase=erase)
        if op == "contains_keys":
            got = cache.contains_keys(blob, len(ks), erase=erase)
            assert got.dtype == bool and got.shape == (len(ks),)
            return op, len(ks), got.tolist()
        return op, 0, cache.discard_key(r.choice(pool))

    for i in range(300):
        step_seed = f"{seed}-{max_entries}-{i}"
        got, want = step(nat), step(py)
        assert got == want, (i, got[0])
        bulk_keys += got[1]
        for attr in _COUNTERS:
            assert getattr(nat, attr) == getattr(py, attr), (i, got[0], attr)
        assert nat.keys_oldest_first() == py.keys_oldest_first(), (i, got[0])
        assert len(nat) == len(py) <= max_entries
    assert nat.evictions > 0 and nat.erases > 0 and nat.hits > 0
    assert nat.insertions - nat.evictions - nat.erases == len(nat)
    for name in _REGISTRY + ("entries",):
        assert _registry(labels[0], name) == _registry(labels[1], name), name
    assert _registry(labels[0], "hits_total") == nat.hits
    assert bulk_keys > 0
    for label, own, other in ((labels[0], "native", "python"),
                              (labels[1], "python", "native")):
        assert _registry(label, "bulk_keys_total", store=own) == bulk_keys
        assert _registry(label, "bulk_keys_total", store=other) == 0


@needs_native
def test_native_set_grows_with_what_it_holds_and_refuses_bad_input():
    """A fresh 1 Mi-entry cache is a few hundred bytes until it is filled
    (the benchmark makes one before every timed connect); a blob shorter
    than the keys asked of it, an index outside it and a key that is not
    32 bytes raise before the set is touched."""
    import time

    import numpy as np

    t0 = time.perf_counter()
    caches = [SigCache(1 << 20, cache_label="grow") for _ in range(200)]
    assert time.perf_counter() - t0 < 1.0  # 40 MB each would take seconds
    cache = caches[0]
    blob = _blob(range(4))
    with pytest.raises(ValueError):
        cache.contains_keys(blob, 5)
    with pytest.raises(IndexError):
        cache.add_keys(blob, np.array([0, 4]))
    with pytest.raises(IndexError):
        cache.add_keys(blob, np.array([-1]))
    for call in (cache.add_key, cache.contains_key, cache.discard_key):
        with pytest.raises(ValueError):
            call(b"\x01" * 31)
    assert len(cache) == 0 and cache.keys_oldest_first() == []
    assert (cache.hits, cache.misses, cache.insertions) == (0, 0, 0)
    cache.add_keys(blob, np.array([3, 0]))
    assert cache.keys_oldest_first() == [_k(3), _k(0)]


@needs_native
def test_native_set_concurrent_bulk_calls_keep_the_accounts():
    """Four threads of bulk inserts, bulk probes with `erase` and discards
    on ONE native-set cache, the interpreter switching every few
    instructions: no entry and no count is lost. `insertions - evictions -
    erases == len(cache)` and `hits + misses == lookups`, in the object and
    in the registry."""
    import os
    import random
    import sys
    import threading

    import numpy as np

    label = "conc-" + os.urandom(4).hex()
    cache = SigCache(max_entries=96, cache_label=label)
    assert cache._nat is not None
    rng = random.Random(7)
    pool = [rng.randbytes(32) for _ in range(400)]
    n_threads, n_ops = 4, 600
    barrier = threading.Barrier(n_threads)
    lookups = [0] * n_threads
    errors = []

    def worker(tid):
        r = random.Random(tid)
        try:
            barrier.wait()
            for _ in range(n_ops):
                ks = r.sample(pool, r.randrange(1, 64))
                blob = b"".join(ks)
                op = r.randrange(4)
                if op == 0:
                    cache.add_keys(blob)
                elif op == 1:
                    cache.add_keys(
                        blob, np.array([r.random() < 0.5 for _ in ks]))
                elif op == 2:
                    hit = cache.contains_keys(blob, len(ks),
                                              erase=r.random() < 0.3)
                    assert hit.shape == (len(ks),)
                    lookups[tid] += len(ks)
                else:
                    cache.discard_key(ks[0])
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    assert not any(t.is_alive() for t in threads)
    keys = cache.keys_oldest_first()
    assert len(keys) == len(set(keys)) == len(cache) <= 96
    assert cache.insertions - cache.evictions - cache.erases == len(cache)
    assert cache.hits + cache.misses == sum(lookups) > 0
    assert cache.evictions > 0 and cache.erases > 0
    for attr, name in zip(_COUNTERS, _REGISTRY[1:]):
        assert _registry(label, name) == getattr(cache, attr), name
    assert _registry(label, "lookups_total") == sum(lookups)


@needs_native
def test_native_set_fault_plan_visits_the_site_once_a_key(monkeypatch):
    """With a `poison` fault armed on `sigcache.sig`, `contains_keys` on a
    native-set cache still goes key by key through `contains_key`: the site
    is asked once a key, in blob order, the absent keys among its first three
    answers are fabricated hits that count as hits, and the set is as it was."""
    from bitcoinconsensus_tpu.models import sigcache as sigcache_mod
    from bitcoinconsensus_tpu.resilience.faults import (
        FaultPlan,
        FaultSpec,
        inject,
        poison_hit,
    )

    cache = SigCache()  # the default label: the site every connect asks
    assert cache._nat is not None and cache._poison_site == "sigcache.sig"
    cache.add_keys(_blob([0, 1]))
    visits = []
    monkeypatch.setattr(
        sigcache_mod._faults, "poison_hit",
        lambda site: (visits.append(site), poison_hit(site))[1],
    )
    ids = [9, 0, 8, 1, 7]
    with inject(FaultPlan([FaultSpec("sigcache.sig", "poison", count=3)])) as inj:
        got = cache.contains_keys(_blob(ids), len(ids))
    assert visits == ["sigcache.sig"] * 5
    assert inj.total_fired() == 3  # on 9, 0 and 8; 0 was there anyway
    assert got.tolist() == [True, True, True, True, False]  # 9 and 8: fabricated
    assert (cache.hits, cache.misses) == (4, 1)
    assert cache.keys_oldest_first() == [_k(0), _k(1)]
    # the plan gone, the same call is one native walk again
    visits.clear()
    assert cache.contains_keys(_blob(ids), len(ids)).tolist() == [
        False, True, False, True, False]
    assert visits == []
