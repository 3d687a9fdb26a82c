#!/usr/bin/env python
"""Measured multi-chip sharded verification run (MULTICHIP_rNN producer).

Unlike `__graft_entry__.dryrun_multichip` (a structural dry run of the
sharded step), this drives a REAL measured workload through
`ShardedSecpVerifier` on a forced n-device mesh and records the result
as a JSON document:

1. **clean**: a mixed batch dispatched over all n devices, timed over
   several warm iterations (lanes/s), verdicts compared bit-for-bit
   against the host-exact oracle;
2. **eviction-and-continue**: an injected device loss (`mesh.shard.1`,
   `evict_after=1`) must evict that device, rebuild the mesh over the
   survivors, re-answer the lost shard's lanes bit-identically, and the
   NEXT batch must flow through the shrunken mesh.

No real multi-chip hardware is assumed: the run pins a virtual n-device
CPU platform (same forcing as tests/conftest.py, so the persistent XLA
compile cache is shared). On a TPU pod slice the same script measures
the real thing — drop the forcing with --no-force.

Usage:
    python scripts/multichip_run.py --out MULTICHIP.json
    python scripts/multichip_run.py --devices 8 --iters 5
    python scripts/multichip_run.py --no-force --devices 4 --lanes 2044

On real chips give the clean run enough lanes for a 512-row tile per
shard (`--lanes 2044` on four devices: 511 real lanes + 1 sentinel each),
or `mesh.shard_kernel` answers `xla` for every shard. The document then
records the dispatches by that answer (the counter's `kernel` label) beside
how many Mosaic kernels the program that ran contains, and
the run fails unless it stayed on the mesh rung with every fallback
counter at zero (`chip_guard.assert_clean`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pin(n_devices: int, force: bool) -> None:
    if not force:
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5,
                    help="timed iterations after warmup (default: 5)")
    ap.add_argument("--lanes", type=int, default=13,
                    help="real lanes in the clean run (default: 13)")
    ap.add_argument("--out", metavar="PATH",
                    help="write the JSON document to this path")
    ap.add_argument("--no-force", action="store_true",
                    help="use the ambient platform instead of forcing a "
                    "virtual CPU mesh (real multi-chip hardware)")
    args = ap.parse_args(argv)

    _pin(args.devices, not args.no_force)
    import jax

    if not args.no_force:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np  # noqa: E402

    import __graft_entry__ as ge
    import chip_guard
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
    from bitcoinconsensus_tpu.parallel import mesh as M
    from bitcoinconsensus_tpu.resilience import FaultPlan, FaultSpec, inject

    devs = jax.devices()
    assert len(devs) >= args.devices, (
        f"need {args.devices} devices, have {len(devs)}x {devs[0].platform}"
    )

    # Mixed kinds (ECDSA / Schnorr / taproot tweak), all valid; 13 lanes
    # pad to 32 rows over 8 shards of 4 (3 real lanes + sentinel on the
    # busy shards), so the eviction trial re-dispatches a 3-lane shard.
    checks = ge._example_checks(13)
    oracle = np.asarray(
        [TpuSecpVerifier(min_batch=8)._host_check(c) for c in checks],
        dtype=bool,
    )
    assert oracle.all(), "workload checks must all be valid"

    # --- clean measured run -------------------------------------------
    clean_checks, clean_oracle = checks, oracle
    if args.lanes != len(checks):
        clean_checks = ge._example_checks(args.lanes)
        host = TpuSecpVerifier(min_batch=8)
        clean_oracle = np.asarray(
            [host._host_check(c) for c in clean_checks], dtype=bool
        )
    sv = M.ShardedSecpVerifier(mesh=M.make_mesh(args.devices))
    # Keep the first call's arguments: the program that ran is lowered
    # again below to count its Mosaic kernels (what ran, not the intent).
    step, step_args = sv._step, []

    def recording_step(*a):
        step_args.append(a)
        return step(*a)

    sv._step = recording_step
    def dispatched():
        return {k: int(M._MESH_DISPATCH.value(kernel=k)) for k in ("pallas", "xla")}

    disp0 = dispatched()
    res, verdict = sv.verify_checks_with_verdict(clean_checks)  # warm/compile
    assert np.array_equal(np.asarray(res, dtype=bool), clean_oracle) and verdict
    walls = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        res, verdict = sv.verify_checks_with_verdict(clean_checks)
        walls.append(time.perf_counter() - t0)
        assert np.array_equal(np.asarray(res, dtype=bool), clean_oracle) and verdict
    if devs[0].platform == "tpu":
        chip_guard.assert_clean(sv, "multichip clean run")
    best = min(walls)
    clean = {
        "lanes": len(clean_checks),
        "shard_rows": int(step_args[0][0].shape[0]) // args.devices,
        "mosaic_kernels_in_program": step.lower(*step_args[0]).as_text().count(
            "tpu_custom_call"
        ),
        "iters": args.iters,
        "wall_s": [round(w, 6) for w in walls],
        "best_s": round(best, 6),
        "lanes_per_s": round(len(clean_checks) / best, 1),
        "bit_identical": True,
        "verdict": bool(verdict),
        "mesh_dispatches": {k: n - disp0[k] for k, n in dispatched().items()},
    }
    print(json.dumps({"clean": clean, "platform": devs[0].platform}),
          file=sys.stderr, flush=True)

    # --- eviction-and-continue trial ----------------------------------
    sv2 = M.ShardedSecpVerifier(mesh=M.make_mesh(args.devices), evict_after=1)
    lost = sv2._shard_device_ids[1]
    ev0 = M._MESH_EVICTIONS.value(device=lost)
    with inject(
        FaultPlan([FaultSpec("mesh.shard.1", "device-loss")]), seed=0
    ) as inj:
        res, verdict = sv2.verify_checks_with_verdict(checks)
    assert inj.total_fired() >= 1, "device-loss fault never fired"
    assert np.array_equal(np.asarray(res, dtype=bool), oracle) and verdict
    assert M._MESH_EVICTIONS.value(device=lost) == ev0 + 1
    survivors = int(sv2.mesh.devices.size)
    assert survivors == args.devices - 1 and lost not in sv2._shard_device_ids
    cont = ge._example_checks(6)
    oracle_c = np.asarray(
        [TpuSecpVerifier(min_batch=8)._host_check(c) for c in cont],
        dtype=bool,
    )
    res_c, verdict_c = sv2.verify_checks_with_verdict(cont)
    cont_ok = bool(
        np.array_equal(np.asarray(res_c, dtype=bool), oracle_c) and verdict_c
    )
    assert cont_ok
    eviction = {
        "evicted_device": lost,
        "devices_after": survivors,
        "bit_identical": True,
        "continued_lanes": len(cont),
        "continued_bit_identical": cont_ok,
    }

    from bitcoinconsensus_tpu.obs import flight

    doc = {
        "n_devices": args.devices,
        "platform": devs[0].platform,
        "forced_virtual_mesh": not args.no_force,
        "dry_run": False,
        "ok": True,
        "clean": clean,
        "eviction": eviction,
        "provenance": flight.provenance(),
    }
    out = json.dumps(doc, indent=2)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(out + "\n")
    print(out)
    print(
        f"# multichip run OK: {args.devices} devices, "
        f"{clean['lanes_per_s']} lanes/s best, eviction continued on "
        f"{survivors} devices",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
