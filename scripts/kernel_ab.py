"""A/B harness for verify-kernel experiments on the live TPU.

Builds one adversarial mixed batch (ECDSA, Schnorr and taproot tweak
lanes, valid and corrupted, from `tpu_differential.build_adversarial_checks`,
with the crafted equal-points tweak the fast adds must defer in lane 0),
packs it at each dispatch shape through the native prep, and times the
Pallas kernel device-side (device-resident args, so the number is
compute + readback without the host upload). Every shape's verdicts and
`needs_host` flags are compared with the XLA reference kernel, which runs
in 512-lane pieces so that it compiles once. Usage:

    python scripts/kernel_ab.py [--lanes 512 2048 8192] [--tile T ...]
                                [--against CHECKOUT]

`--tile` times explicit tiles (lanes a grid step) beside the kernel's own
choice; `--against` also times the `verify_tiles` of another checkout of
this repository (a `git archive` of the commit to beat), same arguments,
same process, same chip. One line a shape and kernel: ms a dispatch, ns a
lane, and whether the verdicts matched.
"""

import argparse
import importlib
import os
import sys
import time
import types

sys.path.insert(0, __file__.rsplit("/", 2)[0])
sys.path.insert(0, __file__.rsplit("/", 1)[0])

_REF_LANES = 512  # the XLA reference's one compiled shape


def build_checks(n):
    """n mixed checks: lane 0 the crafted collision (2G = G + 1·G, which
    the flagged adds defer to the host), the rest adversarial."""
    from tpu_differential import build_adversarial_checks

    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck

    qx, qy = H.G.mul(2).to_affine()
    collision = SigCheck(
        "tweak",
        (qx.to_bytes(32, "big"), qy & 1, H.G_X.to_bytes(32, "big"),
         (1).to_bytes(32, "big")),
    )
    return [collision] + build_adversarial_checks(n - 1, seed=7)


def other_kernel(checkout):
    """`verify_tiles` of another checkout, imported under an alias package
    (its modules import each other relatively, so nothing of it lands on
    this checkout's names)."""
    alias = "kernel_ab_other"
    root = types.ModuleType(alias)
    root.__path__ = [os.path.join(os.path.abspath(checkout), "bitcoinconsensus_tpu")]
    sys.modules[alias] = root
    return importlib.import_module(alias + ".ops.pallas_kernel").verify_tiles


def reference(dargs, lanes):
    """(lanes,) verdicts of the XLA complete-add kernel, a piece at a time."""
    import jax
    import numpy as np

    from bitcoinconsensus_tpu.crypto.jax_backend import _verify_kernel

    kernel = jax.jit(_verify_kernel)
    step = min(_REF_LANES, lanes)
    return np.concatenate([
        np.asarray(kernel(*(a[i : i + step] for a in dargs)))
        for i in range(0, lanes, step)
    ])


def timed(kernel, dargs, **kw):
    """(first call s, best s, median s, ok, needs) of `kernel` on `dargs`."""
    import numpy as np

    t0 = time.perf_counter()
    ok, needs = kernel(*dargs, **kw)
    np.asarray(ok)
    first = time.perf_counter() - t0
    times = []
    for _ in range(7):
        t0 = time.perf_counter()
        ok, needs = kernel(*dargs, **kw)
        ok.block_until_ready()
        needs.block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return first, times[0], times[len(times) // 2], np.asarray(ok), np.asarray(needs)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", nargs="+", type=int, default=[512, 2048, 8192])
    ap.add_argument("--tile", nargs="*", type=int, default=[])
    ap.add_argument("--against", help="another checkout whose kernel to time too")
    a = ap.parse_args(argv)

    import jax
    import numpy as np

    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.ops.pallas_kernel import verify_tiles

    kernels = [("this", verify_tiles, {})]
    kernels += [(f"this tile={t}", verify_tiles, {"tile": t}) for t in a.tile]
    if a.against:
        kernels.append((a.against, other_kernel(a.against), {}))

    checks = build_checks(max(a.lanes))
    failed = False
    for lanes in a.lanes:
        args = native_bridge.prep_pack(checks[:lanes], lanes)
        dargs = [jax.device_put(np.asarray(x)) for x in args]
        ref = reference(dargs, lanes)
        print(f"lanes={lanes} valid={int(np.asarray(args[6]).sum())} "
              f"ref_ok={int(ref.sum())}", flush=True)
        flags = None
        for name, kernel, kw in kernels:
            if kw.get("tile") and lanes % kw["tile"]:
                continue
            first, best, median, ok, needs = timed(kernel, dargs, **kw)
            # A deferred lane reports ok=False and is the host's to answer:
            # everywhere else the verdict is the XLA kernel's, bit for bit,
            # and every kernel defers the same lanes (the crafted one).
            match = (np.array_equal(ok | needs, ref | needs)
                     and not (ok & needs).any() and bool(needs[0])
                     and (flags is None or np.array_equal(needs, flags)))
            flags = needs if flags is None else flags
            failed |= not match
            print(
                f"  {name:24s} first={first:6.1f}s best={best * 1e3:8.3f}ms "
                f"median={median * 1e3:8.3f}ms {best / lanes * 1e9:7.1f}ns/lane "
                f"needs_host={int(needs.sum())} match={match}", flush=True,
            )
    if failed:
        sys.exit("verdict mismatch vs XLA kernel")


if __name__ == "__main__":
    main()
