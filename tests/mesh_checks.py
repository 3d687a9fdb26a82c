"""Standalone multi-chip sharding checks, run in a FRESH process.

Same rationale as pallas_equality_check.py: the 8-device shard_map
programs are among the largest compiles in the suite, and XLA:CPU
intermittently segfaults compiling them late in a long-lived pytest
process (observed inside backend_compile_and_load and in the
compilation-cache read/write paths, with the persistent cache on AND
off, with the native core on AND off — jaxlib-internal; the identical
compile in a clean process always passes). test_parallel.py runs each
check here in its own interpreter; the subprocess uses the persistent
compile cache, so repeat runs are fast.

Usage: python tests/mesh_checks.py {dryrun|sharded|np2|hostreject|faultdomains}...
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


def check_dryrun() -> None:
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def check_sharded() -> None:
    """Sharded == unsharded, incl. failing lanes and the psum verdict."""
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier
    from bitcoinconsensus_tpu.parallel.mesh import ShardedSecpVerifier, make_mesh

    checks = []
    for i in range(8):  # 8 lanes + 8 sentinels: the 16-lane step dryrun compiled
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

    sharded = ShardedSecpVerifier(make_mesh(8))
    res, all_ok = sharded.verify_checks_with_verdict(checks)
    assert not all_ok  # lanes 4 and 5 are corrupted
    assert list(np.nonzero(~res)[0]) == [4, 5]

    good = [c for i, c in enumerate(checks) if i not in (4, 5)]
    res2, ok2 = sharded.verify_checks_with_verdict(good)
    assert res2.all() and ok2  # collective verdict from the psum step

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
    res, all_ok = ShardedSecpVerifier(make_mesh(8)).verify_checks_with_verdict(checks)
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


CHECKS = {
    "dryrun": check_dryrun,
    "sharded": check_sharded,
    "np2": check_np2,
    "hostreject": check_hostreject,
    "faultdomains": check_faultdomains,
}

if __name__ == "__main__":
    from child_checks import main

    sys.exit(main(CHECKS, sys.argv[1:]))
