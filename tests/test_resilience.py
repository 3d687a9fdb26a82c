"""Fail-closed fault containment: injector, guards, ladder, dispatch seam.

Unit coverage of `bitcoinconsensus_tpu/resilience/` plus end-to-end
containment through `TpuSecpVerifier`'s guarded dispatch/settle path.
The device kernel is replaced by a host-exact stand-in here (the
containment machinery is entirely host-side, so a stub exercises every
line of it without paying XLA compiles); the REAL kernels are swept by
`scripts/consensus_chaos.py` and CI's `chaos-smoke` job.

The contract under test (README "Robustness"): an injected fault may
cost retries, ladder demotions, or host re-verification — it must never
change a verdict, and in particular must never corrupt a REJECT into an
ACCEPT.
"""

import hashlib

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier
from bitcoinconsensus_tpu.resilience import degrade as D
from bitcoinconsensus_tpu.resilience import faults as F
from bitcoinconsensus_tpu.resilience import guards as G
from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject

from packed_stub import install_kernel

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls


# ---------------------------------------------------------------------------
# Workload helpers.


def _checks(n, bad_last=True):
    """n valid ECDSA checks; `bad_last` appends a cryptographically-false
    one (wrong message) so every containment test proves a REJECT cannot
    be corrupted into an ACCEPT."""
    out = []
    for i in range(n):
        sk = (i * 2654435761 + 99) % (H.N - 1) + 1
        msg = hashlib.sha256(b"res-%d" % i).digest()
        out.append(
            SigCheck("ecdsa", (H.pubkey_create(sk), H.sign_ecdsa(sk, msg), msg))
        )
    if bad_last:
        sk = 1234567
        signed = hashlib.sha256(b"res-signed").digest()
        shown = hashlib.sha256(b"res-shown").digest()
        out.append(
            SigCheck("ecdsa", (H.pubkey_create(sk), H.sign_ecdsa(sk, signed), shown))
        )
    return out


def _stub_verifier(checks, explode=0):
    """Verifier whose kernel is a host-exact stand-in.

    Real lanes answer from the host oracle, sentinel pad lanes answer
    their precomputed expectations (so the clean path settles exactly as
    a healthy device would), and the first `explode` calls raise — the
    transient/persistent dispatch-failure knob."""
    v = TpuSecpVerifier(min_batch=8)
    oracle = np.asarray([v._host_check(c) for c in checks], dtype=bool)
    # Sentinel templates rotate across dispatches, so the stand-in
    # recognizes each installed lane by its packed bytes (as a real
    # device recomputes it from the fields) instead of assuming order.
    exp_by_raw = {raw: exp for raw, *_rest, exp in G._sentinel_templates()}
    state = {"fails": explode, "calls": 0}

    def kernel(args, n):
        state["calls"] += 1
        F.maybe_raise("jax_backend.dispatch")  # same seam as _run_packed
        if state["fails"] > 0:
            state["fails"] -= 1
            raise RuntimeError("injected dispatch explosion")
        fields, valid = args[0], args[-1]
        padded = int(fields.shape[0])
        ok = np.zeros(padded, dtype=bool)
        ok[:n] = oracle[:n]
        for pos in range(n, padded):
            if valid[pos]:
                ok[pos] = exp_by_raw[fields[pos].tobytes()]
        return ok, np.zeros(padded, dtype=bool)

    install_kernel(v, kernel)
    return v, oracle, state


def _sentinel_args(size=8, readonly=False):
    """A fake packed 7-tuple with `size` lanes for install_sentinels."""
    fields = np.zeros((size, 4, 32), dtype=np.uint8)
    if readonly:
        fields.flags.writeable = False
    flags = [np.zeros(size, dtype=np.int32) for _ in range(5)]
    valid = np.zeros(size, dtype=bool)
    return (fields, *flags, valid)


# ---------------------------------------------------------------------------
# faults: determinism, bounds, arming discipline.


def test_fault_injector_bounded_and_counted():
    plan = FaultPlan([FaultSpec("site.a", "raise", count=2)])
    with inject(plan) as inj:
        for _ in range(2):
            with pytest.raises(F.InjectedFault):
                F.maybe_raise("site.a")
        F.maybe_raise("site.a")  # drained: silent
        F.maybe_raise("site.b")  # wrong site: silent
        assert inj.fired == {("site.a", "raise"): 2}
        assert inj.total_fired() == 2
    assert F.active() is None
    F.maybe_raise("site.a")  # disarmed: silent


def test_fault_injector_timeout_type():
    with inject(FaultPlan([FaultSpec("s", "timeout")])):
        with pytest.raises(F.InjectedTimeout):
            F.maybe_raise("s")


def test_inject_not_reentrant():
    with inject(FaultPlan([])):
        with pytest.raises(RuntimeError):
            with inject(FaultPlan([])):
                pass
    assert F.active() is None  # the failed arm must not wedge the slot


def test_corruption_deterministic_per_seed():
    base = np.zeros(16, dtype=bool)
    spec = [FaultSpec("v", "flip", lanes=4)]

    def corrupt(seed):
        with inject(FaultPlan(spec), seed=seed):
            return F.corrupt_verdict("v", base)

    a, b = corrupt(7), corrupt(7)
    assert np.array_equal(a, b)  # same (plan, seed) -> same fault
    assert a.sum() >= 1  # it actually flipped something


def test_corruption_kinds():
    base = np.ones(8, dtype=bool)
    for kind, check in [
        ("invert", lambda c: not c.any()),
        ("shape", lambda c: c.shape == (7,)),
        ("garbage", lambda c: c.dtype == np.int32),
        ("value", lambda c: 7 in c),
        ("nan", lambda c: np.isnan(c).any()),
    ]:
        with inject(FaultPlan([FaultSpec("v", kind)])):
            got = F.corrupt_verdict("v", base)
        assert check(got), (kind, got)
    # disarmed: the buffer passes through untouched
    assert F.corrupt_verdict("v", base) is base


# ---------------------------------------------------------------------------
# guards: verdict validation + sentinel lanes.


def test_validate_verdict_bool_fast_path():
    a = np.array([True, False, True])
    assert G.validate_verdict(a, 3, "t") is a


def test_validate_verdict_anomaly_classes():
    cases = [
        (np.ones(4, dtype=bool), 5, "shape"),        # truncated
        (np.ones((4, 1), dtype=bool), 4, "shape"),   # wrong rank
        (np.array([0, 1, 7], dtype=np.int32), 3, "domain"),
        (np.array([0.0, np.nan], dtype=np.float32), 2, "nonfinite"),
        (np.array([0.0, 0.5], dtype=np.float32), 2, "domain"),
        (np.array([1 + 0j, 0j]), 2, "dtype"),
    ]
    for arr, n, reason in cases:
        with pytest.raises(G.VerdictAnomaly) as ei:
            G.validate_verdict(arr, n, "t")
        assert ei.value.reason == reason, (arr.dtype, arr.shape)
    ok = G.validate_verdict(np.array([0, 1, 1], dtype=np.int32), 3, "t")
    assert ok.dtype == np.bool_ and ok.tolist() == [False, True, True]


def test_sentinel_install_and_check():
    args = _sentinel_args(size=8)
    sset = G.install_sentinels(args, 5, rotation=0)
    assert sset is not None
    assert sset.positions.tolist() == [5, 6, 7]
    assert sset.expected.tolist() == [True, False, True]
    assert args[-1][5:].all()  # pad lanes marked valid
    assert args[0][5].any()  # fields actually written
    ok = np.zeros(8, dtype=bool)
    ok[sset.positions] = sset.expected
    sset.check(ok, None, "t")  # exact expectations: no raise
    ok[6] = True  # expect-False sentinel came back True
    with pytest.raises(G.VerdictAnomaly) as ei:
        sset.check(ok, None, "t")
    assert ei.value.reason == "sentinel"


def test_sentinel_needs_host_lanes_excluded():
    """A sentinel lane the fast-add kernel deferred reports ok=False by
    design; it must be excluded, not miscounted as corruption."""
    args = _sentinel_args(size=8)
    # rotation pinned: positions 6 (True), 7 (False)
    sset = G.install_sentinels(args, 6, rotation=0)
    ok = np.zeros(8, dtype=bool)  # position 6 WRONG if it were compared
    needs = np.zeros(8, dtype=bool)
    needs[6] = True
    sset.check(ok, needs, "t")  # no raise: lane 6 excluded, lane 7 matches


def test_sentinel_skip_no_room_and_readonly():
    assert G.install_sentinels(_sentinel_args(size=8), 8) is None
    skipped = G._SENTINEL_SKIPPED.value(reason="readonly")
    assert G.install_sentinels(_sentinel_args(size=8, readonly=True), 4) is None
    assert G._SENTINEL_SKIPPED.value(reason="readonly") == skipped + 1


def test_sentinel_rotation_and_writable_copy():
    """Consecutive dispatches carry different expected patterns (a stuck
    replayed buffer mismatches), and read-only batches are packed into a
    fresh buffer whose views take the sentinels, so no dispatch goes out
    sentinel-less."""
    seen = set()
    for _ in range(len(G._SENTINEL_SCALARS)):
        sset = G.install_sentinels(_sentinel_args(size=8), 6)
        seen.add(tuple(sset.expected.tolist()))
    assert len(seen) > 1  # the phase really rotates
    from bitcoinconsensus_tpu.crypto import lane_wire as W

    ro = _sentinel_args(size=8, readonly=True)
    packed = W.pack_lanes(ro, 4)
    args = W._lane_views(packed)[:-1]
    assert all(a.flags.writeable for a in args)
    sset = G.install_sentinels(args, 4, rotation=0)
    assert sset is not None and list(sset.positions) == [4, 5, 6, 7]
    skipped = G._SENTINEL_SKIPPED.value(reason="readonly")
    (packed2, sset2) = TpuSecpVerifier()._pack_ticket(ro, 4)
    assert sset2 is not None and G._SENTINEL_SKIPPED.value(reason="readonly") == skipped
    assert W.unpack_lanes(packed2)[6][4:].all()  # the pad rows hold valid sentinels


def test_verdict_checksum_catches_single_flip():
    """The closed containment floor: a single-lane flip anywhere in the
    buffer — real-lane region included — mismatches the device sums."""
    ok = np.zeros(16, dtype=bool)
    ok[3] = ok[9] = True
    sums = G.verdict_checksum_host(ok)
    G.check_checksum(sums, ok, "t")  # clean: no raise
    G.check_checksum(None, ok, "t")  # checksum-less dispatch: no-op
    for lane in range(16):  # every position is above the floor
        flipped = ok.copy()
        flipped[lane] = not flipped[lane]
        with pytest.raises(G.VerdictAnomaly) as ei:
            G.check_checksum(sums, flipped, "t")
        assert ei.value.reason == "checksum"
    # a swap that preserves the count is caught by the weighted sum
    swapped = ok.copy()
    swapped[3], swapped[4] = False, True
    with pytest.raises(G.VerdictAnomaly):
        G.check_checksum(sums, swapped, "t")


# ---------------------------------------------------------------------------
# degrade: ladder state machine + retry budget.


def test_ladder_demotes_after_streak():
    lad = D.Ladder(("fast", "slow", "host"), "t1", demote_after=2)
    assert lad.pick_level() == ("fast", False)
    lad.report("fast", False)
    assert lad.current == "fast"  # one failure is not a quarantine
    lad.report("fast", True)
    lad.report("fast", False)
    assert lad.current == "fast"  # success reset the streak
    lad.report("fast", False)
    assert lad.current == "slow"
    lad.report("slow", False)
    lad.report("slow", False)
    assert lad.current == "host"
    lad.report("host", False)
    lad.report("host", False)
    assert lad.current == "host"  # bottom rung: nowhere further to go


def test_ladder_probe_and_repromotion():
    lad = D.Ladder(("fast", "host"), "t2", demote_after=1, probe_after=2)
    lad.report("fast", False)
    assert lad.current == "host"
    assert lad.pick_level() == ("host", False)
    lad.report("host", True)
    lad.report("host", True)
    level, probe = lad.pick_level()
    assert (level, probe) == ("fast", True)
    lad.report("fast", False, probe=True)  # failed probe: window re-arms
    assert lad.current == "host"
    assert lad.pick_level() == ("host", False)
    lad.report("host", True)
    lad.report("host", True)
    level, probe = lad.pick_level()
    assert (level, probe) == ("fast", True)
    lad.report("fast", True, probe=True)  # successful probe: re-promoted
    assert lad.current == "fast"


def test_ladder_requires_host_rung():
    with pytest.raises(ValueError):
        D.Ladder(("fast", "slow"), "t3")


def test_retry_budget_attempts_and_deadline():
    res = D.DispatchResilience(("xla", "host"), "t4", max_retries=2,
                               retry_deadline_s=60.0)
    dl = res.deadline()
    assert res.may_retry(1, dl, "t")
    assert res.may_retry(2, dl, "t")
    assert not res.may_retry(3, dl, "t")  # attempts exhausted
    from bitcoinconsensus_tpu.obs import monotonic

    assert not res.may_retry(1, monotonic() - 1.0, "t")  # deadline passed


# ---------------------------------------------------------------------------
# End-to-end containment through the guarded dispatch/settle seam.


def test_guarded_dispatch_clean_path():
    checks = _checks(6)
    v, oracle, state = _stub_verifier(checks)
    lanes_before = G._SENTINEL_LANES.value()
    out = v.verify_checks(checks)
    assert np.array_equal(out, oracle)
    assert not oracle[-1]  # the bad check really is a REJECT
    assert state["calls"] == 1
    assert v._resilience.ladder.current == "xla"
    assert G._SENTINEL_LANES.value() > lanes_before


@pytest.mark.parametrize(
    "kind", ["invert", "flip", "value", "nan", "garbage", "shape"]
)
def test_transient_verdict_corruption_contained(kind):
    checks = _checks(6)
    v, oracle, state = _stub_verifier(checks)
    plan = FaultPlan([FaultSpec("jax_backend.verdict", kind)])
    with inject(plan) as inj:
        out = v.verify_checks(checks)
    assert inj.total_fired() == 1
    assert np.array_equal(out, oracle)
    assert state["calls"] == 2  # one retry absorbed the transient fault
    assert v._resilience.ladder.current == "xla"  # no quarantine


def test_persistent_corruption_quarantines_to_host():
    checks = _checks(6)
    v, oracle, _ = _stub_verifier(checks)
    contained = G.CONTAINED.value(site="jax_backend")
    lanes = G.HOST_EXACT_LANES.value()
    plan = FaultPlan([FaultSpec("jax_backend.verdict", "garbage", count=64)])
    with inject(plan) as inj:
        out = v.verify_checks(checks)
    assert inj.total_fired() >= 2  # retried, then gave up
    assert np.array_equal(out, oracle)
    assert v._resilience.ladder.current == "host"
    assert G.CONTAINED.value(site="jax_backend") == contained + 1
    assert G.HOST_EXACT_LANES.value() == lanes + len(checks)


def test_transient_dispatch_exception_contained():
    checks = _checks(5)
    v, oracle, state = _stub_verifier(checks, explode=1)
    out = v.verify_checks(checks)
    assert np.array_equal(out, oracle)
    assert state["calls"] == 2
    assert v._resilience.ladder.current == "xla"


def test_persistent_dispatch_exception_lands_on_host():
    checks = _checks(5)
    v, oracle, _ = _stub_verifier(checks, explode=1_000_000)
    out = v.verify_checks(checks)
    assert np.array_equal(out, oracle)
    assert v._resilience.ladder.current == "host"


def test_quarantine_heals_via_probe():
    checks = _checks(5)
    v, oracle, state = _stub_verifier(checks, explode=1_000_000)
    v._resilience = D.DispatchResilience(
        v._ladder_levels(), name="heal-test", probe_after=2
    )
    assert np.array_equal(v.verify_checks(checks), oracle)
    assert v._resilience.ladder.current == "host"
    state["fails"] = 0  # the backend recovers
    for _ in range(2):  # earn the probe window on the host rung
        assert np.array_equal(v.verify_checks(checks), oracle)
    assert np.array_equal(v.verify_checks(checks), oracle)  # the probe
    assert v._resilience.ladder.current == "xla"
    assert state["calls"] >= 1


def test_sync_lanes_fail_closed():
    """A chunk no device rung can answer comes back with every lane
    flagged needs_host — the caller's exact oracle decides, never a
    fabricated ACCEPT."""
    checks = _checks(5)
    v, _, _ = _stub_verifier(checks, explode=1_000_000)
    args = v._pack_lanes(v._prep_lanes(checks))
    rec = v.dispatch_lanes(args, len(checks))
    ok, needs = v.sync_lanes(rec, len(checks))
    assert not ok.any()
    assert needs is not None and needs.all()


# ---------------------------------------------------------------------------
# Cache poisoning containment.


def test_poisoned_probe_keeps_cache_invariants():
    from bitcoinconsensus_tpu.models.sigcache import SigCache

    c = SigCache(cache_label="res-poison")
    c.add_check("ecdsa", (b"pk", b"sig", b"msg"))
    plan = FaultPlan([FaultSpec("sigcache.res-poison", "poison")])
    with inject(plan) as inj:
        assert c.contains_check("ecdsa", (b"other", b"sig", b"msg"))  # fabricated
    assert inj.fired == {("sigcache.res-poison", "poison"): 1}
    assert len(c) == 1  # the fabricated hit inserted nothing
    assert c.hits == 1 and c.misses == 0  # counted as a hit: hits+misses==lookups
    assert c.insertions - c.evictions - c.erases == len(c)
    c.discard_key(c._key(c._parts("ecdsa", (b"pk", b"sig", b"msg"))))
    assert len(c) == 0
    assert c.insertions - c.evictions - c.erases == len(c)
    c.discard_key(b"\x00" * 32)  # absent: no-op, invariants still hold
    assert c.insertions - c.evictions - c.erases == len(c)


def test_batch_audit_catches_poisoned_hit():
    """Audit mode: a fabricated sig-cache hit on a cryptographically
    FALSE signature is re-verified on the host oracle, counted, evicted —
    and the verdict stays REJECT."""
    from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_LIBCONSENSUS
    from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
    from bitcoinconsensus_tpu.models.sigcache import (
        ScriptExecutionCache,
        SigCache,
    )
    from test_batch import make_p2wpkh_spend

    def item(seed, corrupt=False):
        txb, spk, amt = make_p2wpkh_spend(seed, corrupt=corrupt)
        return BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                         spent_output_script=spk, amount=amt)

    verifier = TpuSecpVerifier(min_batch=8)
    # Host-exact "device": this test is about the cache path, not the kernel.
    verifier.verify_checks = lambda cks: np.asarray(
        [verifier._host_check(c) for c in cks], dtype=bool
    )
    sig_cache = SigCache()  # label "sig" -> fault site "sigcache.sig"
    script_cache = ScriptExecutionCache(cache_label="res-audit-s")
    caught = G.CACHE_POISON_CAUGHT.value(cache="sig")
    G.set_cache_audit(True)
    try:
        plan = FaultPlan([FaultSpec("sigcache.sig", "poison")])
        with inject(plan) as inj:
            res = verify_batch(
                [item("res-audit-bad", corrupt=True), item("res-audit-good")],
                verifier=verifier, sig_cache=sig_cache,
                script_cache=script_cache,
            )
    finally:
        G.set_cache_audit(False)
    assert inj.total_fired() == 1
    assert [r.ok for r in res] == [False, True]
    assert G.CACHE_POISON_CAUGHT.value(cache="sig") == caught + 1
    assert len(sig_cache) == 1  # only the genuine success was (re)inserted


# ---------------------------------------------------------------------------
# Soak: randomized plans, every iteration must stay bit-identical.


@pytest.mark.slow
def test_chaos_soak_bit_identical():
    import random

    kinds = ["invert", "flip", "value", "nan", "garbage", "shape", "raise",
             "timeout"]
    checks = _checks(6)
    for seed in range(40):
        rng = random.Random(seed)
        specs = [
            FaultSpec(
                "jax_backend.dispatch" if k in ("raise", "timeout")
                else "jax_backend.verdict",
                k, count=rng.randrange(1, 4),
            )
            for k in rng.sample(kinds, rng.randrange(1, 4))
        ]
        v, oracle, _ = _stub_verifier(checks)
        with inject(FaultPlan(specs), seed=seed):
            out = v.verify_checks(checks)
        assert np.array_equal(out, oracle), (seed, specs)
