"""Kernel tests: batched group ops vs the host oracle.

Mirrors the reference's approach of exercising the whole group logic over
adversarial cases (`secp256k1/src/tests_exhaustive.c`): every exceptional
branch of the branchless complete addition laws (P+P, P+(-P), ∞+Q, Q+∞,
digit=0 lanes) is driven explicitly in one batch, so flipping any mask in
`ops/curve.py` fails these tests.
"""

import random

import numpy as np

from conftest import *  # noqa: F401,F403 (pins CPU platform before jax import)

import jax

from bitcoinconsensus_tpu.crypto.secp_host import G, N, P, PointJ
from bitcoinconsensus_tpu.ops.curve import (
    G_X,
    G_Y,
    jacobian_add_complete,
    jacobian_double,
    jacobian_madd_complete,
    jacobian_to_affine,
)
from bitcoinconsensus_tpu.ops.limbs import int_to_limbs, limbs_to_int

RNG = random.Random(0xEC)


def _rand_point():
    k = RNG.randrange(1, N)
    x, y = G.mul(k).to_affine()
    return x, y


def _pack(triples):
    """[(X, Y, Z) ints] -> three limb-major (20, B) arrays."""
    xs = np.stack([int_to_limbs(t[0]) for t in triples], axis=-1).astype(np.int32)
    ys = np.stack([int_to_limbs(t[1]) for t in triples], axis=-1).astype(np.int32)
    zs = np.stack([int_to_limbs(t[2]) for t in triples], axis=-1).astype(np.int32)
    return xs, ys, zs


def _unpack_affine(X, Y, Z):
    """Batched Jacobian triple -> [(x, y) or None] via the device path."""
    x, y, inf = jax.jit(jacobian_to_affine)(X, Y, Z)
    x, y, inf = np.asarray(x), np.asarray(y), np.asarray(inf)
    out = []
    for i in range(x.shape[1]):
        if inf[i]:
            out.append(None)
        else:
            out.append((limbs_to_int(x[:, i]), limbs_to_int(y[:, i])))
    return out


def _oracle_affine(p: PointJ):
    return p.to_affine()  # None when infinity


def _jacobianize(x, y, z_scale):
    """Affine (x, y) -> non-trivial Jacobian representative with Z=z_scale."""
    z2 = z_scale * z_scale % P
    return x * z2 % P, y * z2 * z_scale % P, z_scale


def test_jacobian_double():
    pts = [_rand_point() for _ in range(4)]
    cases = [PointJ.from_affine(*pt) for pt in pts]
    cases.append(PointJ.infinity())
    # Non-trivial Z representative.
    x, y = pts[0]
    cases.append(PointJ(*_jacobianize(x, y, 0xDEADBEEF)))
    # y = 0 cannot occur on secp256k1 (no 2-torsion), so doubling never
    # produces infinity from a finite point — but infinity must map to
    # infinity.
    X, Y, Z = _pack([(c.X, c.Y, c.Z) for c in cases])
    got = _unpack_affine(*jax.jit(jacobian_double)(X, Y, Z))
    want = [_oracle_affine(c.double()) for c in cases]
    assert got == want


def test_madd_complete_all_branches():
    gx, gy = G_X, G_Y
    q1 = _rand_point()
    qx, qy = q1
    z = 0x1234567
    cases = [
        # (jacobian lhs, affine rhs, oracle result)
        (PointJ.from_affine(*_rand_point()), (gx, gy)),        # generic
        (PointJ.from_affine(gx, gy), (gx, gy)),                # P + P (double)
        (PointJ(*_jacobianize(gx, gy, z)), (gx, gy)),          # P + P, Z != 1
        (PointJ.from_affine(gx, (-gy) % P), (gx, gy)),         # P + (-P) = inf
        (PointJ(*_jacobianize(gx, (-gy) % P, z)), (gx, gy)),   # same, Z != 1
        (PointJ.infinity(), (qx, qy)),                         # inf + Q = Q
        (PointJ.from_affine(*_rand_point()), (qx, qy)),        # generic 2
    ]
    X, Y, Z = _pack([(c.X, c.Y, c.Z) for c, _ in cases])
    ax = np.stack([int_to_limbs(a[0]) for _, a in cases], axis=-1).astype(np.int32)
    ay = np.stack([int_to_limbs(a[1]) for _, a in cases], axis=-1).astype(np.int32)
    got = _unpack_affine(*jax.jit(jacobian_madd_complete)(X, Y, Z, ax, ay))
    want = [_oracle_affine(c.add_affine(*a)) for c, a in cases]
    assert got == want


def test_add_complete_all_branches():
    z = 0xABCDEF
    p1 = _rand_point()
    p2 = _rand_point()
    cases = [
        # (lhs PointJ, rhs PointJ, inf2 flag)
        (PointJ.from_affine(*p1), PointJ.from_affine(*p2), False),   # generic
        (PointJ.from_affine(*p1), PointJ(*_jacobianize(*p1, z)), False),  # P+P
        (
            PointJ(*_jacobianize(*p1, z)),
            PointJ.from_affine(p1[0], (-p1[1]) % P),
            False,
        ),  # P + (-P)
        (PointJ.infinity(), PointJ.from_affine(*p2), False),         # inf + Q
        (PointJ.from_affine(*p1), PointJ.infinity(), True),          # Q + inf
        (PointJ.infinity(), PointJ.infinity(), True),                # inf + inf
        (
            PointJ(*_jacobianize(*p1, z)),
            PointJ(*_jacobianize(*p2, 0x77777)),
            False,
        ),  # generic, both Z != 1
    ]
    X1, Y1, Z1 = _pack([(a.X, a.Y, a.Z) for a, _, _ in cases])
    X2, Y2, Z2 = _pack([(b.X, b.Y, b.Z) for _, b, _ in cases])
    inf2 = np.asarray([f for _, _, f in cases], dtype=bool)
    got = _unpack_affine(
        *jax.jit(jacobian_add_complete)(X1, Y1, Z1, X2, Y2, Z2, inf2)
    )
    want = []
    for a, b, f in cases:
        want.append(_oracle_affine(a.add(b if not f else PointJ.infinity())))
    assert got == want
