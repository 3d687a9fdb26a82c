"""Host stand-ins for `crypto.jax_backend`'s one-device packed program.

A one-device dispatch is one packed buffer in and one int32 result out
(`crypto/lane_wire.py`). The verifier's host side (prep, launch, settle,
retry, ladder) is tested in-process without compiling the program:
`install_kernel` puts any function with the old seam's shape, `kernel(args,
n) -> ok | (ok, needs)` over the seven unpacked arrays, behind
`verifier._run_packed`, and answers in the program's own wire format.
(`tests/mesh_stub.py` is the same for the mesh's sharded step.)
"""

import numpy as np

from bitcoinconsensus_tpu.crypto import lane_wire as W
from bitcoinconsensus_tpu.resilience.guards import verdict_checksum_host


def pack_result(ok, needs=None) -> np.ndarray:
    """What the packed program returns for these verdicts: `ok + 2 * needs`
    a row, then the checksum pair over `ok`."""
    ok = np.asarray(ok, dtype=bool)
    rows = ok.astype(np.int32)
    if needs is not None:
        rows = rows + 2 * np.asarray(needs, dtype=bool).astype(np.int32)
    return np.concatenate([rows, np.array(verdict_checksum_host(ok), dtype=np.int32)])


def unpack_result(raw):
    """`(ok, needs, (count, wsum))` of one packed program's result."""
    ok, needs, tail = W.split_result(np.asarray(raw), 1, W.CHECKSUM_TAIL)
    return ok, needs != 0, (int(tail[0, 0]), int(tail[0, 1]))


def install_kernel(verifier, kernel):
    """Stand `kernel(args, n)` in for the device program of `verifier`:
    every launch unpacks its buffer to the seven arrays, asks
    `verifier._run_kernel` (so that a test can wrap it again) and packs the
    answer. The launch's own fault site and accounting are the kernel's to
    mimic, as they were."""
    verifier._run_kernel = kernel

    def run_packed(packed, n):
        answer = verifier._run_kernel(W.unpack_lanes(np.asarray(packed))[:-1], n)
        return pack_result(*(answer if isinstance(answer, tuple) else (answer,)))

    verifier._run_packed = run_packed
    return verifier


def host_lane_verdicts(fields, want_odd, parity, has_t2, neg1, neg2, valid):
    """The kernel's verdict a lane in host integers, for the kernel's seven
    arguments (`jax_backend._verify_kernel`'s docstring is the format):
    lift P from `(px, want_odd)`, `R = a*G + (+-b1 +- lambda*b2)*P`, accept
    `R.x == t1` (or `t1 + n` with `has_t2`) under the parity asked for. For
    the tests whose assertion is about the driver around a dispatch of a
    size the real kernel is not compiled at (`conftest.py`)."""
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.ops.curve import LAMBDA

    def le(b):
        return int.from_bytes(bytes(b), "little")

    ok = np.zeros(len(valid), dtype=bool)
    for i in np.nonzero(valid)[0]:
        a, b1, b2 = le(fields[i, 0]), le(fields[i, 1, :16]), le(fields[i, 1, 16:])
        point = H.lift_x(le(fields[i, 2]), odd=want_odd[i] == 1)
        if point is None:
            continue
        b = ((-b1 if neg1[i] == 1 else b1) + (-b2 if neg2[i] == 1 else b2) * LAMBDA) % H.N
        r = H.G.mul(a).add(H.PointJ.from_affine(*point).mul(b)).to_affine()
        if r is None:
            continue
        t1 = le(fields[i, 3])
        ok[i] = (r[0] == t1 or (has_t2[i] == 1 and r[0] == t1 + H.N)) and (
            parity[i] < 0 or (r[1] & 1) == (parity[i] == 1)
        )
    return ok


def xla_lane_verdicts(*lanes):
    """The one-device XLA program's verdict a lane, for the kernel's seven
    arguments: the packed program at that many rows (the rungs
    `warm_kernel` made are compiled already)."""
    from bitcoinconsensus_tpu.crypto.jax_backend import _packed_program

    raw = _packed_program("xla")(W.pack_lanes(lanes, 0))
    return unpack_result(raw)[0]
