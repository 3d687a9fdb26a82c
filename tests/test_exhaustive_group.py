"""Exhaustive enumeration of the branchless group-law logic.

The reference runs its whole group stack over a tiny exhaustive curve
(`secp256k1/src/tests_exhaustive.c`, windows shrunk at
`ecmult_impl.h:18-31`) for total state-space coverage. The TPU-native
equivalent enumerates, not samples, the *branch space* of the complete
(and flagged) addition laws on the real curve:

1. Every ordered pair (k1·P, k2·P) for k1, k2 over a scalar set chosen
   to realize ALL (z1_zero, inf2, h_zero, r_zero) mask combinations —
   infinity operands, equal points (doubling case), negated points
   (cancellation), generic adds — each point in TWO Jacobian
   representations (Z = 1 and Z = c), against the Python oracle.
2. The same pairs through jacobian_madd_complete (affine right operand)
   and the flagged variants (needs_dbl must fire EXACTLY on the finite
   equal-point case and nowhere else).
3. An exhaustive small-scalar rectangle a, b in [0, N1) x [0, N2)
   through the full GLV double-scalar kernel in one batched dispatch —
   every leading-zero / all-zero-window / infinity-join corner of the
   ladder.
"""

import random

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

import jax.numpy as jnp

from bitcoinconsensus_tpu.crypto import secp_host as H
from bitcoinconsensus_tpu.crypto.glv import split_lambda
from bitcoinconsensus_tpu.ops import curve as C
from bitcoinconsensus_tpu.ops.limbs import NLIMB, P_INT, int_to_limbs

# Scalar set: 0 (infinity), 1, 2 (equal/double pairings), 3, 5 (generic),
# n-1, n-2 (negations -> cancellation pairings).
KS = [0, 1, 2, 3, 5, H.N - 1, H.N - 2]
ZSCALES = [1, 0x1234567]  # Z = 1 and a scaled Jacobian representation


def _points():
    """[(k, affine-or-None)] for the scalar set over G."""
    out = []
    for k in KS:
        pt = H.G.mul(k).to_affine() if k % H.N else None
        out.append((k, pt))
    return out


def _jacobian_lanes(pairs):
    """Build (20, B) limb arrays for a list of (affine_or_None, zscale)
    Jacobian operands; infinity encodes as (1, 1, 0) with its mask."""
    B = len(pairs)
    X = np.zeros((NLIMB, B), dtype=np.int32)
    Y = np.zeros((NLIMB, B), dtype=np.int32)
    Z = np.zeros((NLIMB, B), dtype=np.int32)
    inf = np.zeros(B, dtype=bool)
    one = int_to_limbs(1)
    for i, (pt, zs) in enumerate(pairs):
        if pt is None:
            X[:, i] = one
            Y[:, i] = one
            inf[i] = True
            continue
        x, y = pt
        z2 = zs * zs % P_INT
        X[:, i] = int_to_limbs(x * z2 % P_INT)
        Y[:, i] = int_to_limbs(y * z2 * zs % P_INT)
        Z[:, i] = int_to_limbs(zs)
    return jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z), inf


def _affine_ints(x_limbs, y_limbs, inf_mask):
    x = np.asarray(x_limbs)
    y = np.asarray(y_limbs)
    out = []
    for i in range(x.shape[1]):
        if inf_mask[i]:
            out.append(None)
            continue
        xi = sum(int(x[j, i]) << (13 * j) for j in range(NLIMB))
        yi = sum(int(y[j, i]) << (13 * j) for j in range(NLIMB))
        out.append((xi, yi))
    return out


def _expected_add(k1, k2):
    k = (k1 + k2) % H.N
    return H.G.mul(k).to_affine() if k else None


def test_complete_add_every_branch_combination():
    """All (k1, k2, z1-rep, z2-rep) pairings through
    jacobian_add_complete, with explicit and computed inf1 masks."""
    pts = _points()
    lanes1, lanes2, expect, tags = [], [], [], []
    for k1, p1 in pts:
        for k2, p2 in pts:
            for z1 in ZSCALES:
                for z2 in ZSCALES:
                    lanes1.append((p1, z1))
                    lanes2.append((p2, z2))
                    expect.append(_expected_add(k1, k2))
                    tags.append((k1, k2, z1, z2))

    X1, Y1, Z1, inf1 = _jacobian_lanes(lanes1)
    X2, Y2, Z2, inf2 = _jacobian_lanes(lanes2)

    # inf1 as explicit mask (loop-tracked form) and as computed (None).
    for with_mask in (True, False):
        if with_mask:
            X, Y, Z, out_inf = C.jacobian_add_complete(
                X1, Y1, Z1, X2, Y2, Z2, jnp.asarray(inf2), inf1=jnp.asarray(inf1)
            )
            out_inf = np.asarray(out_inf)
        else:
            X, Y, Z = C.jacobian_add_complete(
                X1, Y1, Z1, X2, Y2, Z2, jnp.asarray(inf2)
            )
            out_inf = None
        x, y, got_inf = C.jacobian_to_affine(X, Y, Z)
        got_inf = np.asarray(got_inf)
        got = _affine_ints(x, y, got_inf)
        for i, (want, tag) in enumerate(zip(expect, tags, strict=True)):
            assert (got[i] is None) == (want is None), (tag, "infinity", with_mask)
            if want is not None:
                assert got[i] == want, (tag, "value", with_mask)
            if out_inf is not None:
                assert bool(out_inf[i]) == (want is None), (tag, "inf flag")


def test_flagged_add_defers_exactly_the_doubling_case():
    pts = _points()
    lanes1, lanes2, expect_flag, expect_val, tags = [], [], [], [], []
    for k1, p1 in pts:
        for k2, p2 in pts:
            for z1 in ZSCALES:
                lanes1.append((p1, z1))
                lanes2.append((p2, 1))
                # finite equal points (including k1 == k2 through different
                # representations) -> deferral; everything else computes.
                flag = p1 is not None and p2 is not None and k1 % H.N == k2 % H.N
                expect_flag.append(flag)
                expect_val.append(None if flag else _expected_add(k1, k2))
                tags.append((k1, k2, z1))

    X1, Y1, Z1, inf1 = _jacobian_lanes(lanes1)
    X2, Y2, Z2, inf2 = _jacobian_lanes(lanes2)
    X, Y, Z, out_inf, needs = C.jacobian_add_flagged(
        X1, Y1, Z1, X2, Y2, Z2, jnp.asarray(inf2), jnp.asarray(inf1)
    )
    needs = np.asarray(needs)
    out_inf = np.asarray(out_inf)
    x, y, _ = C.jacobian_to_affine(X, Y, Z, inf=jnp.asarray(out_inf | needs))
    got = _affine_ints(x, y, out_inf | needs)
    for i, (flag, want, tag) in enumerate(zip(expect_flag, expect_val, tags, strict=True)):
        assert bool(needs[i]) == flag, (tag, "needs_dbl")
        if flag:
            continue
        assert (got[i] is None) == (want is None), (tag, "infinity")
        if want is not None:
            assert got[i] == want, (tag, "value")


def test_complete_and_flagged_madd_all_pairings():
    """Mixed adds: affine right operand (never infinity)."""
    pts = _points()
    finite = [(k, p) for k, p in pts if p is not None]
    lanes1, rx, ry, expect, flags, tags = [], [], [], [], [], []
    for k1, p1 in pts:
        for k2, p2 in finite:
            for z1 in ZSCALES:
                lanes1.append((p1, z1))
                rx.append(p2[0])
                ry.append(p2[1])
                expect.append(_expected_add(k1, k2))
                flags.append(p1 is not None and k1 % H.N == k2 % H.N)
                tags.append((k1, k2, z1))

    X1, Y1, Z1, inf1 = _jacobian_lanes(lanes1)
    B = len(rx)
    x2 = jnp.asarray(
        np.stack([int_to_limbs(v) for v in rx], axis=1).astype(np.int32)
    )
    y2 = jnp.asarray(
        np.stack([int_to_limbs(v) for v in ry], axis=1).astype(np.int32)
    )

    X, Y, Z, out_inf = C.jacobian_madd_complete(
        X1, Y1, Z1, x2, y2, inf1=jnp.asarray(inf1)
    )
    out_inf = np.asarray(out_inf)
    x, y, _ = C.jacobian_to_affine(X, Y, Z, inf=jnp.asarray(out_inf))
    got = _affine_ints(x, y, out_inf)
    for i, (want, tag) in enumerate(zip(expect, tags, strict=True)):
        assert (got[i] is None) == (want is None), (tag, "infinity")
        if want is not None:
            assert got[i] == want, (tag, "value")

    Xf, Yf, Zf, inf_f, needs = C.jacobian_madd_flagged(
        X1, Y1, Z1, x2, y2, inf1=jnp.asarray(inf1)
    )
    needs = np.asarray(needs)
    inf_f = np.asarray(inf_f)
    xf, yf, _ = C.jacobian_to_affine(Xf, Yf, Zf, inf=jnp.asarray(inf_f | needs))
    gotf = _affine_ints(xf, yf, inf_f | needs)
    for i, (want, flag, tag) in enumerate(zip(expect, flags, tags, strict=True)):
        assert bool(needs[i]) == flag, (tag, "needs_dbl")
        if flag:
            continue
        assert (gotf[i] is None) == (want is None), (tag, "infinity")
        if want is not None:
            assert gotf[i] == want, (tag, "value")


def test_double_every_point():
    pts = _points()
    lanes = [(p, z) for _, p in pts for z in ZSCALES]
    ks = [k for k, _ in pts for _ in ZSCALES]
    X, Y, Z, inf = _jacobian_lanes(lanes)
    Xd, Yd, Zd = C.jacobian_double(X, Y, Z)
    x, y, got_inf = C.jacobian_to_affine(Xd, Yd, Zd)
    got_inf = np.asarray(got_inf)
    got = _affine_ints(x, y, got_inf)
    for i, k in enumerate(ks):
        want = H.G.mul(2 * k % H.N).to_affine() if (2 * k) % H.N else None
        assert (got[i] is None) == (want is None), (k, "infinity")
        if want is not None:
            assert got[i] == want, k


@pytest.mark.limit(600)  # a cold compile of minutes beside five other workers
def test_exhaustive_small_scalar_rectangle_through_glv_kernel():
    """Every (a, b) in [0, 24) x [0, 24) through the GLV double-scalar
    schedule in ONE batch: a·G + b·P vs the oracle. Covers all-zero
    windows, b = 0 (pure fixed-base), a = 0 (pure variable-base), and
    the infinity join combinations exhaustively. Behind the rectangle, in
    the same batch, full-width scalars on other points: every window of
    both halves live, a cancelling pair, sparse digits, the top bits."""
    N1 = N2 = 24
    sk = 7  # P = 7·G, arbitrary small point
    rng = random.Random(0xEC)
    big = lambda: rng.randrange(1, H.N)  # noqa: E731
    lanes = [(a, b, sk) for a in range(N1) for b in range(N2)] + [
        (big(), big(), big()),                # generic
        (0, big(), big()),                    # a = 0 (RG infinite)
        (big(), 0, big()),                    # b = 0 (R infinite)
        (5, H.N - 5, 1),                      # aG + bG = inf
        (0x8000, 0x10, big()),                # sparse digits
        ((1 << 256) % H.N, H.N - 1, big()),   # high bits set
    ]
    B = len(lanes)

    a_l = np.zeros((NLIMB, B), dtype=np.int32)
    px = np.zeros((NLIMB, B), dtype=np.int32)
    py = np.zeros((NLIMB, B), dtype=np.int32)
    b1m = np.zeros((10, B), dtype=np.int32)
    b2m = np.zeros((10, B), dtype=np.int32)
    neg1 = np.zeros(B, dtype=bool)
    neg2 = np.zeros(B, dtype=bool)
    points = {}
    for i, (a, b, k) in enumerate(lanes):
        if k not in points:
            points[k] = tuple(int_to_limbs(c) for c in H.G.mul(k).to_affine())
        px[:, i], py[:, i] = points[k]
        a_l[:, i] = int_to_limbs(a)
        a1, n1, a2, n2 = split_lambda(b)
        b1m[:, i] = int_to_limbs(a1, 10)
        b2m[:, i] = int_to_limbs(a2, 10)
        neg1[i] = bool(n1)
        neg2[i] = bool(n2)

    X, Y, Z, out_inf = C.double_scalar_mult_glv(
        jnp.asarray(a_l),
        C._digits128(jnp.asarray(b1m)),
        C._digits128(jnp.asarray(b2m)),
        jnp.asarray(neg1),
        jnp.asarray(neg2),
        jnp.asarray(px),
        jnp.asarray(py),
    )
    x, y, _ = C.jacobian_to_affine(X, Y, Z, inf=out_inf)
    out_inf = np.asarray(out_inf)
    got = _affine_ints(x, y, out_inf)
    for i, (a, b, k) in enumerate(lanes):
        r = (a + b * k) % H.N
        want = H.G.mul(r).to_affine() if r else None
        assert (got[i] is None) == (want is None), (a, b, k, "infinity")
        if want is not None:
            assert got[i] == want, (a, b, k)
