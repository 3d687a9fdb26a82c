"""Standalone pallas-vs-XLA equality checks, run in a FRESH process.

Why a subprocess: the interpret-mode pallas compiles are the largest XLA
programs in the suite (`verify_tiles` alone traces, lowers and compiles
for 12 minutes on an idle core), and XLA:CPU segfaults compiling (or
cache-writing) them late in a long-lived pytest process that has already
compiled ~100 other programs — reproducibly at `tests/test_pallas_kernel.py`, and
reproducibly NOT when the same compile runs in a clean process (the crash
is inside jaxlib, with the native core disabled too).

Checks that compile the same programs share a process: `small` and
`collision` both run the `tile=16` interpret-mode `verify_tiles` (two
sublane rows of 8 lanes, so the CPU walks a tile with S > 1) and compare
it with the one-device XLA program at 16 lanes (`_xla_kernel`), so
`test_pallas_kernel.py` starts them as one child with one large compile
(`production`, the 512-lane tile of four rows of 128 lanes, is a `slow`
test with a child of its own). Each check is reported by name on a line of
its own, so each stays a test of its own.

Usage: python tests/pallas_equality_check.py {small|production|collision}...
Exit code 0 = every named check passed.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

from child_checks import warm_rung  # noqa: E402
from packed_stub import xla_lane_verdicts  # noqa: E402


def _xla_kernel(*lanes):
    """The XLA side as the verifier dispatches it: the one-device packed
    program on the kernel's seven arrays. At 16 lanes that is the executable
    `warm_kernel`'s second rung puts into the persistent cache: this child
    loads it from there on a second thread beside the Pallas side's compile
    (`child_checks.warm_rung_beside`), so the call here finds it loaded, or
    waits for a worker that is still compiling it; tier-1 compiles the XLA
    kernel in no other form (`conftest.py`)."""
    assert len(lanes[-1]) == 16
    warm_rung(16)
    return xla_lane_verdicts(*lanes)


def check_small() -> None:
    """tile=16 (two rows of 8 lanes) adversarial mix: bit-equality with
    the XLA kernel, a corruption of every flavor in each row."""
    import __graft_entry__ as ge
    from bitcoinconsensus_tpu.ops.pallas_kernel import verify_tiles

    fields, want_odd, parity, has_t2, neg1, neg2, valid = ge._example_arrays(16)
    fields = np.array(fields)
    want_odd = np.array(want_odd)
    valid = np.array(valid)
    neg1 = np.array(neg1)

    fields[3, 3, 0] ^= 1  # corrupt lane 3's target -> must fail
    valid[5] = False  # structurally invalid lane
    fields[7, 2, 0] ^= 1  # perturb lane 7's pubkey x (likely non-residue)
    want_odd[2] ^= 1  # wrong y parity for lane 2's pubkey -> wrong R
    neg1[4] ^= 1  # flip a GLV half sign -> wrong R for lane 4
    fields[11, 3, 0] ^= 1  # the second row: a target,
    valid[13] = False  # an invalid lane
    want_odd[10] ^= 1  # and a wrong lift

    got_ok, got_needs = verify_tiles(
        fields, want_odd, parity, has_t2, neg1, neg2, valid,
        tile=16, interpret=True,
    )
    got = np.asarray(got_ok)
    want = _xla_kernel(fields, want_odd, parity, has_t2, neg1, neg2, valid)
    assert not np.asarray(got_needs).any()  # no group-law deferrals here
    assert (got == want).all(), (got, want)
    bad = [2, 3, 4, 5, 10, 11, 13]
    assert not want[bad].any(), want[bad]
    assert want[[0, 1, 8, 9, 15]].all(), want


def check_production() -> None:
    """Equality at the PRODUCTION tile (LANE_TILE=512): multi-kind lanes
    (ECDSA/Schnorr/tweak), adversarial corruptions of every flavor, and —
    crucially — the 128-lane rows of `_tile_batch_inv` (seven tree levels
    each way where the tile=16 check walks three) and the (4, 128) tile."""
    import __graft_entry__ as ge
    from bitcoinconsensus_tpu.crypto.jax_backend import (
        SigCheck,
        TpuSecpVerifier,
        _verify_kernel,
    )
    from bitcoinconsensus_tpu.ops.pallas_kernel import LANE_TILE, verify_tiles

    checks = ge._example_checks(LANE_TILE)
    # Structurally-invalid lanes (host-rejected, valid=False): bad ECDSA
    # pubkey prefix; short Schnorr pubkey.
    d = checks[9].data
    checks[9] = SigCheck("ecdsa", (b"\x05" + d[0][1:], d[1], d[2]))
    d = checks[10].data
    checks[10] = SigCheck("schnorr", (d[0][:31], d[1], d[2]))

    v = TpuSecpVerifier(min_batch=LANE_TILE)
    args = v._pack_lanes(v._prep_lanes(checks))
    fields, want_odd, parity, has_t2, neg1, neg2, valid = (
        np.array(a) for a in args
    )
    assert not valid[9] and not valid[10]
    # Device-level corruptions across kinds (lane i: i%3==0 ECDSA,
    # 1 Schnorr, 2 tweak).
    fields[0, 3, 0] ^= 1  # ECDSA target
    fields[1, 3, 0] ^= 1  # Schnorr target
    fields[2, 3, 0] ^= 1  # tweak target
    fields[3, 2, 0] ^= 1  # ECDSA pubkey x perturbed (likely non-residue)
    want_odd[6] ^= 1  # ECDSA wrong y-lift parity
    parity[4] ^= 1  # Schnorr R.y parity requirement flipped
    neg1[12] ^= 1  # GLV half sign flip

    want = np.asarray(
        _verify_kernel(fields, want_odd, parity, has_t2, neg1, neg2, valid)
    )
    got_ok, got_needs = verify_tiles(
        fields, want_odd, parity, has_t2, neg1, neg2, valid,
        tile=LANE_TILE, interpret=True,
    )
    got = np.asarray(got_ok)
    assert not np.asarray(got_needs).any()
    assert (got == want).all(), np.nonzero(got != want)
    bad = [0, 1, 2, 3, 4, 6, 9, 10, 12]
    assert not want[bad].any(), want[bad]
    # _pack_lanes pads past LANE_TILE (the sentinel reservation means
    # LANE_TILE real lanes need the next chunk size): only the real-lane
    # prefix must verify; pad lanes are valid=False and must all fail.
    mask = np.zeros(want.size, dtype=bool)
    mask[:LANE_TILE] = True
    mask[bad] = False
    assert want[mask].all(), np.nonzero(~want & mask)
    assert not want[LANE_TILE:].any(), "pad lanes must not verify"


def check_collision() -> None:
    """A crafted equal-points taproot tweak: the pallas fast adds must
    flag the lane needs_host (ok=False), others unaffected; the XLA
    complete kernel resolves it TRUE directly."""
    import __graft_entry__ as ge
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier
    from bitcoinconsensus_tpu.ops.pallas_kernel import verify_tiles

    qx, qy = H.G.mul(2).to_affine()
    collision = SigCheck(
        "tweak",
        (
            qx.to_bytes(32, "big"),
            qy & 1,
            H.G_X.to_bytes(32, "big"),
            (1).to_bytes(32, "big"),
        ),
    )
    checks = ge._example_checks(15)  # both rows of the tile hold live lanes
    checks[0] = collision
    v = TpuSecpVerifier(min_batch=16)
    args = v._pack_lanes(v._prep_lanes(checks))

    ok, needs = verify_tiles(*args, tile=16, interpret=True)
    ok, needs = np.asarray(ok), np.asarray(needs)
    assert needs[0] and not ok[0], "collision lane must defer"
    assert not needs[1:15].any() and ok[1:15].all(), "others unaffected"

    want = _xla_kernel(*args)
    assert want[:15].all()  # XLA complete kernel: collision resolves TRUE


CHECKS = {
    "small": check_small,
    "production": check_production,
    "collision": check_collision,
}

if __name__ == "__main__":
    from child_checks import main

    # `small` and `collision` compare with the 16-lane rung: it loads beside
    # the interpret-mode compile, not behind it
    names = sys.argv[1:]
    sys.exit(main(CHECKS, names, beside=16 if "production" not in names else None))
