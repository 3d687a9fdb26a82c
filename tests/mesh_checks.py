"""Standalone multi-chip sharding checks, run in a FRESH process.

Same rationale as pallas_equality_check.py: the shard_map programs are
among the largest compiles in the suite, and XLA:CPU intermittently
segfaults compiling them late in a long-lived pytest process (observed
inside backend_compile_and_load and in the compilation-cache read/write
paths, with the persistent cache on AND off, with the native core on AND
off — jaxlib-internal; the identical compile in a clean process always
passes). test_parallel.py runs the checks here in children of their own.

Tier-1 compiles two mesh programs: the four-device step at 16 lanes (every
check but `np2` and the `slow` `faultdomains`; the four-chip cell's mesh)
and `np2`'s six-device one. The checks on four devices share one child,
which keeps ONE jitted step a mesh (`_one_step_a_mesh`). Eight virtual
devices still run in `contrib/test.sh` and CI
(`__graft_entry__.dryrun_multichip(8)`), `scripts/multichip_run.py` and
the `slow` `faultdomains`.

Usage: python tests/mesh_checks.py {dryrun|sharded|np2|hostreject|faultdomains|connect|connectflip|packing}...
Exit code 0 = every named check passed.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import hashlib  # noqa: E402

import numpy as np  # noqa: E402

from child_checks import warm_rung  # noqa: E402


def check_dryrun() -> None:
    import __graft_entry__ as g

    g.dryrun_multichip(4)


def check_sharded() -> None:
    """Sharded == unsharded, incl. failing lanes and the psum verdict."""
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier
    from bitcoinconsensus_tpu.parallel.mesh import ShardedSecpVerifier, make_mesh

    checks = []
    for i in range(8):  # 8 lanes + 4 sentinels: the 16-lane step dryrun compiled
        sk = (i * 7919 + 3) % (H.N - 1) + 1
        msg = hashlib.sha256(b"shard-%d" % i).digest()
        if i % 2:
            xpk, _ = H.xonly_pubkey_create(sk)
            sig = H.sign_schnorr(sk, msg)
            if i == 5:
                sig = sig[:8] + bytes([sig[8] ^ 1]) + sig[9:]
            checks.append(SigCheck("schnorr", (xpk, sig, msg)))
        else:
            pub = H.pubkey_create(sk)
            sig = H.sign_ecdsa(sk, msg)
            if i == 4:
                msg = hashlib.sha256(b"other").digest()
            checks.append(SigCheck("ecdsa", (pub, sig, msg)))

    sharded = ShardedSecpVerifier(make_mesh(4))
    res, all_ok = sharded.verify_checks_with_verdict(checks)
    assert not all_ok  # lanes 4 and 5 are corrupted
    assert list(np.nonzero(~res)[0]) == [4, 5]

    good = [c for i, c in enumerate(checks) if i not in (4, 5)]
    res2, ok2 = sharded.verify_checks_with_verdict(good)
    assert res2.all() and ok2  # collective verdict from the psum step

    warm_rung(16)  # the one-device program: wait for a worker that compiles it
    plain = TpuSecpVerifier().verify_checks(checks)
    assert np.array_equal(plain, res)


def check_np2() -> None:
    """A 6-device mesh must not hang (ADVICE r1 medium) and must agree."""
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
    from bitcoinconsensus_tpu.parallel.mesh import ShardedSecpVerifier, make_mesh

    checks = []
    for i in range(5):
        sk = (i * 104729 + 11) % (H.N - 1) + 1
        msg = hashlib.sha256(b"np2-%d" % i).digest()
        checks.append(
            SigCheck("ecdsa", (H.pubkey_create(sk), H.sign_ecdsa(sk, msg), msg))
        )

    sharded = ShardedSecpVerifier(make_mesh(6))
    assert sharded._min_batch % 6 == 0
    res, all_ok = sharded.verify_checks_with_verdict(checks)
    assert res.all() and all_ok
    assert all(sharded._host_check(c) for c in checks)  # the host oracle agrees


def check_hostreject() -> None:
    """A lane that fails host-side structural parsing (never dispatched)
    must still flip the block verdict to False."""
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
    from bitcoinconsensus_tpu.parallel.mesh import ShardedSecpVerifier, make_mesh

    sk = 12345
    msg = hashlib.sha256(b"hr").digest()
    checks = [
        SigCheck("ecdsa", (H.pubkey_create(sk), H.sign_ecdsa(sk, msg), msg)),
        SigCheck("ecdsa", (b"\x02" + b"\x00" * 31, b"junk-not-der", msg)),
    ]
    # min_batch=16: two checks on four devices would pad to an 8-lane step
    # of their own; this is the 16-lane one the other checks compile
    sharded = ShardedSecpVerifier(make_mesh(4), min_batch=16)
    res, all_ok = sharded.verify_checks_with_verdict(checks)
    assert list(res) == [True, False]
    assert not all_ok


def check_faultdomains() -> None:
    """Shard fault domains on the REAL kernels: a single-shard verdict
    flip is convicted by THAT shard's checksum and only its lanes
    re-dispatch; a device loss evicts the device, the mesh rebuilds over
    the 7 survivors, and verification continues bit-identically."""
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
    from bitcoinconsensus_tpu.parallel import mesh as M
    from bitcoinconsensus_tpu.resilience import guards as G
    from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject

    def mk(n, tag):
        out = []
        for i in range(n):
            sk = (i * 6700417 + 29) % (H.N - 1) + 1
            msg = hashlib.sha256(b"fd-%s-%d" % (tag, i)).digest()
            out.append(
                SigCheck("ecdsa", (H.pubkey_create(sk), H.sign_ecdsa(sk, msg), msg))
            )
        return out

    # Every check is valid, so the host oracle stands in for the unsharded
    # kernel (which `sharded` compares against) at no compile.
    checks = mk(8, b"a")
    v = M.ShardedSecpVerifier(M.make_mesh(8))
    oracle = np.asarray([v._host_check(c) for c in checks])
    assert oracle.all()

    # 1) Clean sharded run (warms the 16-lane 8-device step).
    res, ok = v.verify_checks_with_verdict(checks)
    assert np.array_equal(res, oracle) and ok

    # 2) Single-shard flip: the per-shard checksum convicts shard 2 alone
    #    and only its (one) lane re-dispatches over the surviving mesh.
    flips0 = M._MESH_SHARD_FAILURES.value(device="2", reason="checksum")
    redisp0 = M._MESH_REDISPATCH_LANES.value(level="mesh")
    with inject(FaultPlan([FaultSpec("mesh.shard.2", "flip")])) as inj:
        res, ok = v.verify_checks_with_verdict(checks)
    assert inj.total_fired() >= 1
    assert np.array_equal(res, oracle) and ok
    assert M._MESH_SHARD_FAILURES.value(
        device="2", reason="checksum"
    ) == flips0 + 1
    assert M._MESH_REDISPATCH_LANES.value(level="mesh") == redisp0 + 1

    # 3) Straggler: the per-shard deadline (armed — shape seen) convicts
    #    the slow shard without waiting; verdicts stay bit-identical.
    dl0 = G.GUARD_ANOMALIES.value(site="mesh.shard.0", reason="deadline")
    with inject(
        FaultPlan([FaultSpec("mesh.shard.0", "straggle", value=9e9)])
    ) as inj:
        res, ok = v.verify_checks_with_verdict(checks)
    assert inj.total_fired() >= 1
    assert np.array_equal(res, oracle) and ok
    assert G.GUARD_ANOMALIES.value(
        site="mesh.shard.0", reason="deadline"
    ) == dl0 + 1

    # 4) Device loss with evict_after=1: device 1 leaves the mesh, the
    #    step re-jits over 7 survivors, and the NEXT batch still flows.
    v2 = M.ShardedSecpVerifier(M.make_mesh(8), evict_after=1)
    ev0 = M._MESH_EVICTIONS.value(device="1")
    with inject(
        FaultPlan([FaultSpec("mesh.shard.1", "device-loss")])
    ) as inj:
        res, ok = v2.verify_checks_with_verdict(checks)
    assert inj.total_fired() >= 1
    assert np.array_equal(res, oracle) and ok
    assert M._MESH_EVICTIONS.value(device="1") == ev0 + 1
    assert int(v2.mesh.devices.size) == 7 and "1" not in v2._shard_device_ids
    cont = mk(7, b"b")
    oracle7 = np.asarray([v2._host_check(c) for c in cont])
    res7, ok7 = v2.verify_checks_with_verdict(cont)
    assert np.array_equal(res7, oracle7) and ok7
    print("faultdomains: flip contained, straggler convicted, "
          "eviction continued on 7 devices")


# -- the mesh verifier on the normal path: `connect_block` ---------------------
#
# The block of the four-chip cell (`benchmarks/configs/worst-block-mesh4.json`)
# at its rehearsal size, 15 inputs of a 1-of-20 CHECKMULTISIG = 300 pairings,
# through a four-device mesh at the 16-lane shape: 12 real lanes a dispatch,
# three and a sentinel a shard, 25 dispatches a round against a queue four deep.

_WORST_SEED = 2**31 + 33


def _worst_block():
    """(configuration, traffic data) of the rehearsal-size block, with the
    verifier's shapes cut to the 16-lane rung the suite keeps warm."""
    from benchmarks import run
    from benchmarks.generators import worstblock

    spec = run.load_spec("worst-block-mesh4.sigops", rehearsal=True)
    config = run.merge(spec["config"], {"verifier": {"min_batch": 16, "chunk": 16}})
    return config, worstblock.build(config, spec["traffic"], _WORST_SEED, 0.0)


def _connect_worst(raw, config, d, verifier):
    """One connect on a fresh view and fresh caches: the result, the
    signature cache's size and the view afterwards."""
    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
    from bitcoinconsensus_tpu.models.validate import connect_block

    view = native_bridge.NativeCoinsView()
    view.add_coins_batch(d["coins"])
    sig_cache = SigCache()
    res = connect_block(
        raw, view, d["height"], pow_limit=int(config["block"]["pow_limit"], 16),
        verifier=verifier, sig_cache=sig_cache, script_cache=ScriptExecutionCache(),
    )
    return res, len(sig_cache), view


def _view_facts(view, d):
    """What a connect leaves of the view, in plain data: its size, which of
    the block's coins are still there, which of its outputs arrived."""
    from bitcoinconsensus_tpu.core.tx import OutPoint, Tx

    spent = [view.get(OutPoint(c[0], c[1])) is not None for c in d["coins"]]
    made = [view.get(OutPoint(Tx.deserialize(t["raw"]).txid, 0)) is not None
            for t in d["txs"]]
    return len(view), spent, made


def _mesh_rose(before=None):
    from bitcoinconsensus_tpu.obs import get_registry

    names = ("consensus_dispatch_total", "consensus_mesh_dispatch_total",
             "consensus_inflight_backpressure_total",
             "consensus_mesh_shard_failures_total",
             "consensus_mesh_redispatch_lanes_total",
             "consensus_mesh_verdict_mismatch_total",
             "consensus_exact_fallback_total")
    snap = get_registry().snapshot()
    now = {n: sum(s["value"] for s in snap.get(n, {"samples": []})["samples"])
           for n in names}
    return now if before is None else {n: now[n] - before[n] for n in names}


def check_connect() -> None:
    """Mesh == base verifier == the plain reference == the host oracle, on
    the same seeded block: verdicts, sigop cost, one signature-cache entry an
    input, the view; the corrupted twin rejected for exactly its victim."""
    from benchmarks.drivers.connect_mesh import make_verifier
    from benchmarks.harness import oracle, sigopref
    from bitcoinconsensus_tpu.core.flags import height_to_flags
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
    from bitcoinconsensus_tpu.parallel import mesh as M

    config, d = _worst_block()
    n, lanes = d["n_inputs"], d["pairings"]
    assert (n, lanes) == (15, 300)
    mesh_v = make_verifier(config)
    assert int(mesh_v.mesh.devices.size) == 4 and mesh_v.lane_capacity == 12
    warm_rung(16)  # the one-device program: wait for a worker that compiles it
    base_v = TpuSecpVerifier(min_batch=16, chunk=16)
    dispatches = -(-lanes // mesh_v.lane_capacity)  # 25

    before = _mesh_rose()
    pieces = {way: M._MESH_TRANSFERS.value(dir=way) for way in ("in", "out")}
    res, cached, view = _connect_worst(d["block"], config, d, mesh_v)
    rose = _mesh_rose(before)
    # one packed buffer in and one packed result out, a piece a shard
    for way, was in pieces.items():
        assert M._MESH_TRANSFERS.value(dir=way) == was + 4 * dispatches, way
    # every dispatch sharded (on CPU devices the shards run the XLA kernel),
    # all but the queue's four waiting for the oldest ticket, none failing
    assert rose["consensus_dispatch_total"] == dispatches
    assert rose["consensus_mesh_dispatch_total"] == dispatches
    assert rose["consensus_inflight_backpressure_total"] == dispatches - 4
    assert rose["consensus_mesh_shard_failures_total"] == 0
    assert rose["consensus_mesh_redispatch_lanes_total"] == 0
    assert rose["consensus_mesh_verdict_mismatch_total"] == 0
    assert rose["consensus_exact_fallback_total"] == 0
    phases = mesh_v.phases.report()
    for name in ("shard_layout", "shard_put", "shard_exec", "shard_check"):
        assert phases[name]["calls"] == dispatches, name
    assert phases["dispatch"]["calls"] == dispatches
    assert phases["backpressure"]["calls"] == dispatches - 4
    assert mesh_v._inflight.depth == 0
    assert mesh_v._resilience.ladder.current == "mesh"

    base_res, base_cached, base_view = _connect_worst(d["block"], config, d, base_v)
    triples = [oracle.as_triple(r) for r in res.input_results]
    assert res.ok and base_res.ok and len(triples) == n
    assert triples == [oracle.as_triple(r) for r in base_res.input_results]
    assert res.sigop_cost == base_res.sigop_cost == lanes
    assert cached == base_cached == n  # success-only: the pairing that verified
    assert _view_facts(view, d) == _view_facts(base_view, d)
    assert _view_facts(view, d)[1:] == ([False] * n, [True] * len(d["txs"]))

    # the plain reference: its own count, its own walk over its own curve
    # code; and the host oracle, input by input
    parsed = [(sigopref.parse_tx(t["raw"]), t["outs"]) for t in d["txs"]]
    assert sigopref.block_sigop_cost(sigopref.parse_tx(d["coinbase"]), parsed) == lanes
    flags = height_to_flags(d["height"], extended=True)
    at = 0
    for (tx, outs), t in zip(parsed, d["txs"]):
        for index in range(len(outs)):
            tried, ok = sigopref.p2wsh_multisig_input(tx, index, outs[index])
            assert ok and len(tried) == 20
            assert triples[at] == oracle.oracle_verdict(t["raw"], index, outs, flags)
            at += 1

    # the corrupted twin, on both verifiers: rejected for its victim alone,
    # the view untouched, and the victim's verdict the oracle's
    bad = d["bad_tx"]
    want = oracle.oracle_verdict(
        bad["raw"], d["victim"] - d["tx_start"][bad["index"]], bad["outs"], flags)
    assert not want[0]
    for v in (mesh_v, base_v):
        res, _cached, view = _connect_worst(d["bad_block"], config, d, v)
        assert not res.ok and res.reason == "block-validation-failed"
        assert res.script_failures == [d["victim"]]
        assert oracle.as_triple(res.input_results[d["victim"]]) == want
        assert len(view) == n
    print(f"connect: {dispatches} mesh dispatches a round, mesh == base == reference == oracle")


def check_connectflip() -> None:
    """One lane flipped on shard 2 of one dispatch inside a connect: that
    shard's checksum convicts it, only its three lanes re-dispatch (over the
    mesh), and the block's verdicts, cost and cache are what they were."""
    from benchmarks.drivers.connect_mesh import make_verifier
    from benchmarks.harness import oracle
    from bitcoinconsensus_tpu.parallel import mesh as M
    from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject

    config, d = _worst_block()
    v = make_verifier(config)
    clean, cached, _view = _connect_worst(d["block"], config, d, v)
    assert clean.ok and cached == d["n_inputs"]

    dev = v._shard_device_ids[2]
    flips0 = M._MESH_SHARD_FAILURES.value(device=dev, reason="checksum")
    before = _mesh_rose()
    with inject(FaultPlan([FaultSpec("mesh.shard.2", "flip")])) as inj:
        res, cached, view = _connect_worst(d["block"], config, d, v)
    rose = _mesh_rose(before)
    assert inj.total_fired() == 1
    assert M._MESH_SHARD_FAILURES.value(device=dev, reason="checksum") == flips0 + 1
    assert rose["consensus_mesh_shard_failures_total"] == 1
    assert rose["consensus_mesh_redispatch_lanes_total"] == 3  # shard 2's real lanes
    assert M._MESH_REDISPATCH_LANES.value(level="mesh") >= 3
    assert rose["consensus_mesh_dispatch_total"] == 25 + 1  # the re-dispatch is sharded too
    assert res.ok and res.sigop_cost == clean.sigop_cost and cached == d["n_inputs"]
    assert ([oracle.as_triple(r) for r in res.input_results]
            == [oracle.as_triple(r) for r in clean.input_results])
    assert len(view) == len(_view)
    assert int(v.mesh.devices.size) == 4 and v._resilience.ladder.current == "mesh"
    print("connectflip: shard 2 convicted by its checksum, 3 lanes re-dispatched")


def check_packing() -> None:
    """The compiled four-device program on one packed buffer: its unpack
    ops under the mesh's sharding give what the host unpack gives, and its
    one result unpacks to the five results of the step it replaced: the
    one-device kernel's verdicts, no deferral, the AND of the live lanes, a
    checksum pair a shard. Four pieces in, four out."""
    import jax

    import __graft_entry__ as ge
    from benchmarks.drivers.connect_mesh import make_verifier
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
    from bitcoinconsensus_tpu.parallel import mesh as M
    from bitcoinconsensus_tpu.resilience.guards import verdict_checksum_host
    from mesh_stub import traced_unpack
    from packed_stub import xla_lane_verdicts

    config, _d = _worst_block()
    v = make_verifier(config)
    warm_rung(16)  # `xla_lane_verdicts` below: wait for a worker that compiles it
    unpack = traced_unpack(v.mesh)
    # mixed kinds (ECDSA's parity is -1, don't-care); 12 lanes fill the four
    # shards, 7 leave the last one empty; one bad signature, one not
    for n, bad in ((12, None), (12, 4), (7, 1)):
        checks = ge._example_checks(n)
        if bad is not None:
            pk32, sig64, msg = checks[bad].data
            checks[bad] = SigCheck(
                "schnorr", (pk32, sig64[:40] + bytes([sig64[40] ^ 1]) + sig64[41:], msg))
        lanes = v._pack_lanes(v._prep_lanes(checks))
        assert (lanes[2][:n] == -1).any() and (lanes[2][:n] != -1).any()
        (packed,), layout = v._build_layout(lanes, n)
        assert packed.shape == (16, M.ROW_BYTES) and layout.shard_size == 4
        host = M.unpack_lanes(packed)
        for got, want in zip(unpack(jax.device_put(packed, v._packed_sharding)), host):
            assert got.dtype == want.dtype and np.array_equal(np.asarray(got), want)
        assert list(np.nonzero(host[7])[0]) == list(layout.positions)

        pieces = {way: M._MESH_TRANSFERS.value(dir=way) for way in ("in", "out")}
        raw = np.asarray(v._run_step(packed))
        for way, was in pieces.items():
            assert M._MESH_TRANSFERS.value(dir=way) == was + 4, way
        assert raw.dtype == np.int32 and raw.shape == (16 + 3 * 4,)
        ok, needs, all_ok, cnts, wsums = M.unpack_result(raw, 4)
        want_ok = xla_lane_verdicts(*host[:7])  # the one-device XLA program
        assert np.array_equal(ok, want_ok) and not needs.any()
        assert list(ok[layout.positions]) == [i != bad for i in range(n)]
        assert all_ok is (bad is None) and all_ok == bool(ok[host[7]].all())
        for s, part in enumerate(np.split(want_ok, 4)):
            assert (int(cnts[s]), int(wsums[s])) == verdict_checksum_host(part), s
        layout.flat_sset.check(ok, None, "packing")
    print("packing: device unpack == host unpack, one result == the five, 4 + 4 pieces")


CHECKS = {
    "dryrun": check_dryrun,
    "sharded": check_sharded,
    "np2": check_np2,
    "hostreject": check_hostreject,
    "faultdomains": check_faultdomains,
    "connect": check_connect,
    "connectflip": check_connectflip,
    "packing": check_packing,
}

def _one_step_a_mesh() -> None:
    """Every `ShardedSecpVerifier` builds a jit object of its own for the
    step, and a second one in a process traces, lowers and loads the same
    program again: two minutes and more on the CPU (step 0 of PR 44: `dryrun`
    225 s and `sharded` 146 s behind `hostreject`'s compile, on cache hits).
    Here the process keeps one step a mesh, whoever builds the verifier: a
    stand-in for a cache that belongs in `parallel/mesh.py` (ROADMAP D6);
    until it is there, no tier-1 check runs two verifiers that each build
    a step of their own, as two of them in one program do."""
    import functools

    from bitcoinconsensus_tpu.parallel import mesh as M

    M.make_sharded_step = functools.lru_cache(maxsize=None)(M.make_sharded_step)


if __name__ == "__main__":
    from child_checks import main

    _one_step_a_mesh()
    # the checks that compare with the one-device program at 16 lanes find it
    # loaded beside the step's compile, not behind it
    names = sys.argv[1:]
    compares = {"sharded", "connect", "connectflip", "packing"}.intersection(names)
    sys.exit(main(CHECKS, names, beside=16 if compares else None))
