"""Multi-chip sharding tests: fresh-subprocess compiles + in-process
fault-domain logic.

The shard_map programs are among the suite's largest compiles and XLA:CPU
intermittently segfaults compiling them late in a long-lived pytest
process (see tests/mesh_checks.py for the full evidence trail); the
identical compiles in a clean process always pass.

The shard fault-domain machinery (per-shard checksums/sentinels at
settle, shard-granular re-dispatch, device eviction/re-promotion) is
entirely host-side, so it is exercised here in-process against a
host-exact stand-in step — same stub philosophy as test_resilience —
while `mesh_checks.py faultdomains` drives the REAL kernels through the
identical paths in a clean process.
"""

import hashlib
import os

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from child_checks import Children
from mesh_stub import host_step, pack_result
from packed_stub import install_kernel

from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
from bitcoinconsensus_tpu.parallel import mesh as M
from bitcoinconsensus_tpu.resilience import degrade as D
from bitcoinconsensus_tpu.resilience import guards as G
from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject

_HELPER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "mesh_checks.py")
# All the checks but `np2` run on one program, the four-device step at 16
# lanes (the four-chip cell's mesh), so they share ONE child, which compiles
# it once, keeps one jitted step and loads the one-device program once:
# `hostreject`, `dryrun` and `sharded` through `verify_checks`, then the
# rehearsal-size block of the four-chip cell through `connect_block`
# (`connect`, `connectflip`), then the program alone on one packed buffer
# (`packing`). `sharded` and what follows compare with the one-device
# program, which the workers have compiled by then. `np2` compiles the
# six-device step. Limits: no more than the issue's 800 s; the children's
# cold times under the tier-1 command in the sandbox are in CHANGES.md, PR 44.
# A child killed at its limit fails every check behind the one that was
# running.
_CHILDREN = {
    ("hostreject", "dryrun", "sharded", "connect", "connectflip", "packing"): 800,
    ("np2",): 570,
}

# the file's first test starts the children; the checks that wait for them
# run last of their worker's files (`conftest.py` `_KERNEL_SCOPES`)
pytestmark = pytest.mark.usefixtures("children")


@pytest.fixture(scope="session")
def children(tmp_path_factory):
    """The children, for as long as the session."""
    with Children(_HELPER, _CHILDREN, tmp_path_factory.mktemp("mesh")) as started:
        yield started


@pytest.mark.parametrize("check", [
    # jit + run of the sharded step and the API-facing verifier, 4 devices
    pytest.param("dryrun", marks=pytest.mark.limit(830)),
    # sharded == unsharded `verify_checks`, failing lanes and the psum verdict
    pytest.param("sharded", marks=pytest.mark.limit(830)),
    # a 6-device mesh must not hang and must agree
    pytest.param("np2", marks=pytest.mark.limit(600)),
    # a lane rejected on the host still flips the block verdict
    pytest.param("hostreject", marks=pytest.mark.limit(830)),
    # `connect_block` on a 4-device mesh == base verifier == reference == oracle
    pytest.param("connect", marks=pytest.mark.limit(830)),
    # a flipped lane inside a connect: one shard convicted, its lanes alone re-dispatched
    pytest.param("connectflip", marks=pytest.mark.limit(830)),
    # the compiled program: its unpack == the host's, its one result == the five
    pytest.param("packing", marks=pytest.mark.limit(830)),
])
def test_mesh_on_real_kernels(children, check):
    """`tests/mesh_checks.py <check>` in its fresh process: the sharded step
    compiled and run on forced host devices, through `verify_checks` and
    through `connect_block`."""
    children.expect(check)


@pytest.mark.slow  # 12 minutes and 22 CPU-minutes of compiles, alone and cold
@pytest.mark.limit(1530)
def test_shard_fault_domains_real_kernels(tmp_path):
    with Children(_HELPER, {("faultdomains",): 1500}, tmp_path) as child:
        child.expect("faultdomains")


# ---------------------------------------------------------------------------
# In-process fault-domain harness: the sharded step is replaced by a
# host-exact stand-in (answers every lane from its packed raw bytes, with
# correct per-shard checksum pairs), so settle-seam policy — containment,
# partial settlement, eviction — runs without a single XLA compile.


def _fd_checks(n, bad_last=True):
    out = []
    for i in range(n):
        sk = (i * 2654435761 + 4242) % (H.N - 1) + 1
        msg = hashlib.sha256(b"fd-%d" % i).digest()
        out.append(
            SigCheck("ecdsa", (H.pubkey_create(sk), H.sign_ecdsa(sk, msg), msg))
        )
    if bad_last:
        sk = 7654321
        signed = hashlib.sha256(b"fd-signed").digest()
        shown = hashlib.sha256(b"fd-shown").digest()
        out.append(
            SigCheck("ecdsa", (H.pubkey_create(sk), H.sign_ecdsa(sk, signed), shown))
        )
    return out


def _mesh_stub_verifier(checks, n_devices=8, evict_after=None):
    """ShardedSecpVerifier whose mesh step AND single-device kernel are
    host-exact stand-ins keyed by packed lane bytes (scatter layouts make
    positional keying wrong — a real device recomputes from the fields).
    Survives mesh rebuilds: `_install_mesh` is wrapped to re-install the
    stub after the (lazy, never-executed) re-jit."""
    v = M.ShardedSecpVerifier(
        mesh=M.make_mesh(n_devices), min_batch=8, evict_after=evict_after
    )
    oracle = np.asarray([v._host_check(c) for c in checks], dtype=bool)
    packed = v._pack_lanes(v._prep_lanes(checks))
    by_raw = {
        np.asarray(packed[0][i]).tobytes(): bool(oracle[i])
        for i in range(len(checks))
    }
    by_raw.update(
        {raw: exp for raw, *_rest, exp in G._sentinel_templates()}
    )

    def lane_verdicts(fields, *flags_then_valid):
        valid = flags_then_valid[-1]
        padded = int(fields.shape[0])
        ok = np.zeros(padded, dtype=bool)
        for pos in range(padded):
            if valid[pos]:
                ok[pos] = by_raw.get(np.asarray(fields[pos]).tobytes(), False)
        return ok

    step = host_step(v, lane_verdicts)

    def kernel(args, n):
        ok = lane_verdicts(*args)
        return ok, np.zeros(len(ok), dtype=bool)

    v._step = step
    install_kernel(v, kernel)

    def install(mesh):
        M.ShardedSecpVerifier._install_mesh(v, mesh)
        v._step = step

    v._install_mesh = install
    return v, oracle


def test_make_mesh_rejects_oversubscription():
    with pytest.raises(ValueError, match="requested 9 devices"):
        M.make_mesh(9)


def test_shard_ladder_evicts_and_reprobes():
    lad = D.ShardLadder(["0", "1", "2"], evict_after=2, reprobe_after=3)
    assert not lad.report_shard("1", ok=False)  # first strike
    assert lad.report_shard("1", ok=False)      # second: evict now
    lad.evict("1")
    assert lad.healthy() == ["0", "2"]
    # A clean shard resets its own strike count.
    assert not lad.report_shard("0", ok=False)
    assert not lad.report_shard("0", ok=True)
    assert not lad.report_shard("0", ok=False)
    # Every reprobe_after-th consecutive clean dispatch nominates the
    # longest-evicted device; a dirty dispatch resets the streak.
    assert lad.note_clean_dispatch() is None
    lad.report_shard("2", ok=False)
    for _ in range(2):
        assert lad.note_clean_dispatch() is None
    assert lad.note_clean_dispatch() == "1"
    lad.repromote("1")
    assert lad.healthy() == ["0", "1", "2"]


def test_shard_ladder_never_empties_mesh():
    lad = D.ShardLadder(["0"], evict_after=1)
    assert not lad.report_shard("0", ok=False)  # min_devices floor


def test_mesh_stub_matches_oracle_and_verdict():
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks)
    res, verdict = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res, dtype=bool), oracle)
    assert not verdict  # bad_last lane
    good = _fd_checks(9, bad_last=False)
    v2, oracle2 = _mesh_stub_verifier(good)
    res2, verdict2 = v2.verify_checks_with_verdict(good)
    assert np.array_equal(np.asarray(res2, dtype=bool), oracle2) and verdict2


# 8 devices at the 16- and 32-lane shapes: a shard holds 1 or 3 real lanes
# beside its sentinel. Sizes that fill every shard, leave shards empty, and
# end part-way through one.
_LAYOUT_SIZES = {"one-lane": 1, "underfill": 3, "fill-16": 8, "straddle-32": 13,
                 "fill-32": 24, "straddle-64": 30}


@pytest.mark.parametrize("n", list(_LAYOUT_SIZES.values()), ids=list(_LAYOUT_SIZES))
def test_layout_round_trips(n):
    """Lane i's verdict comes back at i: every third check is bad, the
    layout scatters them shard-major and the settle gathers them back."""
    checks = []
    for i, good in enumerate(_fd_checks(n, bad_last=False)):
        checks.append(good if i % 3 != 1 else _fd_checks(0)[0])
    v, oracle = _mesh_stub_verifier(checks)
    assert list(oracle) == [i % 3 != 1 for i in range(n)]
    res, verdict = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res, dtype=bool), oracle)
    assert verdict == bool(oracle.all())
    assert M._MESH_VERDICT_MISMATCH.value() == 0


@pytest.mark.parametrize("n", [1, 3, 8, 13, 24])
def test_build_layout_is_shard_major(n):
    """Out of read-only buffers, into fresh ones: shard s holds lanes
    [s*cap, (s+1)*cap) at the head of its slice, then pad rows, then its
    sentinel; `positions` and `live` say where the real lanes went."""
    v = M.ShardedSecpVerifier(mesh=M.make_mesh(8), min_batch=8)
    checks = _fd_checks(n, bad_last=False)
    packed = v._pack_lanes(v._prep_lanes(checks))
    padded = int(packed[0].shape[0])
    want_odd = np.arange(1, 1 + padded, dtype=np.int32)  # a tag a row, inside a byte
    src = (packed[0], want_odd) + tuple(packed[2:])
    for a in src:
        a.flags.writeable = False
    (packed,), layout = v._build_layout(src, n)
    assert packed.shape == (padded, M.ROW_BYTES) and packed.dtype == np.uint8
    *args, live = M.unpack_lanes(packed)
    shard, cap = padded // 8, padded // 8 - 1
    assert (layout.n, layout.padded, layout.n_shards, layout.shard_size) == (n, padded, 8, shard)
    assert all(a.dtype == b.dtype and a.shape == b.shape for a, b in zip(args, src))
    assert list(layout.positions) == [(i // cap) * shard + i % cap for i in range(n)]
    assert list(np.nonzero(live)[0]) == list(layout.positions)
    assert list(args[1][layout.positions]) == list(range(1, 1 + n))
    assert np.array_equal(args[0][layout.positions], src[0][:n])
    sentinels = [s * shard + cap for s in range(8)]
    assert list(layout.flat_sset.positions) == sentinels and all(args[6][sentinels])
    pad = sorted(set(range(padded)) - set(layout.positions) - set(sentinels))
    assert not args[6][pad].any() and not args[0][pad].any()
    assert (args[2][pad] == -1).all()  # parity: don't-care
    assert v._build_layout(src, padded) is None  # no room for the sentinels


def test_single_shard_flip_convicted_by_checksum_and_contained():
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks)
    before = {
        d: M._MESH_SHARD_FAILURES.value(device=d, reason="checksum")
        for d in v._shard_device_ids
    }
    redisp0 = M._MESH_REDISPATCH_LANES.value(level="mesh")
    with inject(FaultPlan([FaultSpec("mesh.shard.2", "flip")])) as inj:
        res, verdict = v.verify_checks_with_verdict(checks)
    assert inj.total_fired() >= 1
    # Verdicts bit-identical despite the flip; conviction localized to
    # shard 2's device; only that shard's lanes re-dispatched.
    assert np.array_equal(np.asarray(res, dtype=bool), oracle)
    assert not verdict
    assert M._MESH_SHARD_FAILURES.value(
        device="2", reason="checksum"
    ) == before["2"] + 1
    for d in v._shard_device_ids:
        if d != "2":
            assert M._MESH_SHARD_FAILURES.value(
                device=d, reason="checksum"
            ) == before[d], f"device {d} wrongly convicted"
    # 14 lanes over 8 shards of size 4 -> 3 real lanes on shard 2.
    assert M._MESH_REDISPATCH_LANES.value(level="mesh") == redisp0 + 3


def test_shard_straggler_deadline_is_armed_after_first_dispatch():
    checks = _fd_checks(9, bad_last=False)
    v, oracle = _mesh_stub_verifier(checks)
    dl0 = G.GUARD_ANOMALIES.value(site="mesh.shard.0", reason="deadline")
    # First dispatch compiles in the real world: the straggler deadline
    # must NOT be armed for an unseen padded shape.
    with inject(FaultPlan([FaultSpec("mesh.shard.0", "straggle", value=9e9)])):
        res, _ = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res, dtype=bool), oracle)
    assert G.GUARD_ANOMALIES.value(
        site="mesh.shard.0", reason="deadline"
    ) == dl0
    # Same shape again: armed — the straggling shard is convicted and its
    # lanes re-answered elsewhere, bit-identically.
    with inject(FaultPlan([FaultSpec("mesh.shard.0", "straggle", value=9e9)])) as inj:
        res2, verdict2 = v.verify_checks_with_verdict(checks)
    assert inj.total_fired() >= 1
    assert np.array_equal(np.asarray(res2, dtype=bool), oracle) and verdict2
    assert G.GUARD_ANOMALIES.value(
        site="mesh.shard.0", reason="deadline"
    ) == dl0 + 1


def test_device_loss_evicts_rebuilds_and_continues():
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks, evict_after=1)
    ev0 = M._MESH_EVICTIONS.value(device="1")
    with inject(
        FaultPlan([FaultSpec("mesh.shard.1", "device-loss")])
    ) as inj:
        res, verdict = v.verify_checks_with_verdict(checks)
    assert inj.total_fired() >= 1
    assert np.array_equal(np.asarray(res, dtype=bool), oracle)
    assert not verdict
    # Device 1 evicted; the mesh rebuilt over the 7 survivors and the
    # NEXT batch flows through the shrunken mesh bit-identically.
    assert M._MESH_EVICTIONS.value(device="1") == ev0 + 1
    assert int(v.mesh.devices.size) == 7
    assert "1" not in v._shard_device_ids
    res2, _ = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res2, dtype=bool), oracle)


def test_evicted_device_repromoted_after_clean_probe():
    checks = _fd_checks(9, bad_last=False)
    v, oracle = _mesh_stub_verifier(checks, evict_after=1)
    with inject(FaultPlan([FaultSpec("mesh.shard.3", "raise")])):
        v.verify_checks_with_verdict(checks)
    assert int(v.mesh.devices.size) == 7
    rp0 = M._MESH_REPROMOTIONS.value(device="3")
    v._probe_device = lambda dev_id: True  # known-answer probe passes
    v._shard_ladder.reprobe_after = 1
    res, verdict = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res, dtype=bool), oracle) and verdict
    assert M._MESH_REPROMOTIONS.value(device="3") == rp0 + 1
    assert int(v.mesh.devices.size) == 8 and "3" in v._shard_device_ids
    # And the regrown mesh still answers correctly.
    res2, _ = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res2, dtype=bool), oracle)


def test_failed_probe_keeps_device_quarantined():
    checks = _fd_checks(9, bad_last=False)
    v, oracle = _mesh_stub_verifier(checks, evict_after=1)
    with inject(FaultPlan([FaultSpec("mesh.shard.3", "raise")])):
        v.verify_checks_with_verdict(checks)
    v._probe_device = lambda dev_id: False
    v._shard_ladder.reprobe_after = 1
    res, _ = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res, dtype=bool), oracle)
    assert int(v.mesh.devices.size) == 7


def test_out_of_order_shard_settlement():
    checks = _fd_checks(9, bad_last=False)
    v, oracle = _mesh_stub_verifier(checks)
    h1 = v.verify_checks_begin(checks)
    h2 = v.verify_checks_begin(checks)
    out2 = np.asarray(v.verify_checks_finish(h2), dtype=bool)
    out1 = np.asarray(v.verify_checks_finish(h1), dtype=bool)
    assert np.array_equal(out1, oracle) and np.array_equal(out2, oracle)
    assert v._inflight.depth == 0


def test_out_of_order_settlement_with_shard_fault():
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks)
    with inject(FaultPlan([FaultSpec("mesh.shard.4", "garbage")])) as inj:
        h1 = v.verify_checks_begin(checks)
        h2 = v.verify_checks_begin(checks)
        out2 = np.asarray(v.verify_checks_finish(h2), dtype=bool)
        out1 = np.asarray(v.verify_checks_finish(h1), dtype=bool)
    assert inj.total_fired() >= 1
    assert np.array_equal(out1, oracle) and np.array_equal(out2, oracle)


def test_failed_verify_does_not_poison_next_verdict():
    """Regression: a raising verify_checks used to leave _verdict_acc /
    _dispatched / _fixup_failed stale, corrupting the NEXT call's
    verdict."""
    checks = _fd_checks(9, bad_last=False)
    v, oracle = _mesh_stub_verifier(checks)

    def boom(_checks):
        # Simulate a mid-verify explosion after partial accumulation.
        v._verdict_acc = False
        v._dispatched = 3
        v._fixup_failed = True
        raise RuntimeError("mid-verify explosion")

    v.verify_checks = boom
    with pytest.raises(RuntimeError, match="mid-verify explosion"):
        v.verify_checks_with_verdict(checks)
    del v.verify_checks  # restore the class method
    res, verdict = v.verify_checks_with_verdict(checks)
    assert np.array_equal(np.asarray(res, dtype=bool), oracle)
    assert verdict, "stale accumulators poisoned a clean verdict"


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(4))
def test_mesh_chaos_soak(seed):
    """Multi-seed soak over every shard-scoped fault class: a faulted
    shard may cost re-dispatch, eviction, or host lanes — verdicts must
    stay bit-identical to the oracle."""
    checks = _fd_checks(13)
    kinds = [
        (f"mesh.shard.{s}", k)
        for s in (0, 2, 7)
        for k in ("flip", "invert", "garbage", "shape", "raise",
                  "timeout", "device-loss")
    ]
    kinds += [("mesh.dispatch", "raise")]
    for site, kind in kinds:
        v, oracle = _mesh_stub_verifier(checks)
        with inject(FaultPlan([FaultSpec(site, kind)]), seed=seed) as inj:
            res, verdict = v.verify_checks_with_verdict(checks)
        assert inj.total_fired() >= 1, (site, kind)
        assert np.array_equal(np.asarray(res, dtype=bool), oracle), (
            site, kind, seed,
        )
        assert not verdict  # bad_last lane always present
