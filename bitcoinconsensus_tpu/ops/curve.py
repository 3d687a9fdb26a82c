"""Batched secp256k1 group ops and double-scalar multiplication for TPU.

Points are Jacobian triples ``(X, Y, Z)`` of weak field elements in the
limb-major layout of `limbs.py` — shape ``(20, B)`` with the batch in the
lane axis; ``Z ≡ 0`` encodes infinity. All control flow is branchless:
exceptional cases of the addition law (equal points, negated points,
infinity operands) are computed alongside the generic formula and chosen
with masks, so one traced program is consensus-exact for *every* lane —
the TPU-native replacement for the reference's per-case branches in
`secp256k1/src/group_impl.h`.

The verify workload is R = a·G + b·P per lane (`secp256k1_ecmult`,
`secp256k1/src/ecmult_impl.h:561-580`). The reference runs Strauss-wNAF
per call on one core; here every lane advances in lockstep on the VPU:

- fixed-base half a·G: 32 8-bit windows against a device-resident table
  of affine multiples k·256^w·G (the ecmult_context_build analogue,
  `gen_gtable.py`) — 32 complete mixed additions, zero doublings; the
  one-hot row select runs as an exact f32 matmul on the MXU;
- variable-base half b·P: per-lane Jacobian table {0..15}·P built by a
  14-step `lax.scan`, then, with b split by the GLV endomorphism into two
  signed 128-bit halves on the host, 32 windows of 4 doublings + two
  complete Jacobian additions with a one-hot table select
  (`double_scalar_mult_glv`, the one ladder);
- one final complete addition joins the halves.

No secret data is involved on the verify path, so uniform (non-constant-
time) schedules are fine — same stance as the reference's variable-time
verify routines.
"""

from __future__ import annotations

import os

import numpy as np

import jax.numpy as jnp
from jax import lax

from .regions import named_region
from .limbs import (
    MASK,
    NLIMB,
    RADIX,
    fe_add,
    fe_batch_inv,
    fe_canon,
    fe_inv,
    fe_is_zero,
    fe_is_zero_many,
    fe_mul,
    fe_mul_small,
    fe_sqr,
    fe_sub,
    int_to_limbs,
)

__all__ = [
    "G_X",
    "G_Y",
    "BETA",
    "LAMBDA",
    "GLV_WINDOWS",
    "jacobian_double",
    "jacobian_madd_complete",
    "jacobian_add_complete",
    "double_scalar_mult_glv",
    "jacobian_to_affine",
    "scalar_bits",
]

G_X = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
G_Y = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

# GLV endomorphism: beta^3 = 1 mod p, lambda^3 = 1 mod n, and
# lambda*(x, y) = (beta*x, y) (secp256k1/src/scalar_impl.h:60-112,
# field beta at secp256k1.c / util docs). The verify kernel splits the
# variable-base scalar b = b1 + lambda*b2 with |b1|,|b2| < 2^128
# (host-side, `crypto/glv.py`) and runs 32 4-bit windows instead of 64 —
# halving the doubling count, the dominant cost of the scalar mult.
BETA = 0x7AE96A2B657C07106E64479EAC3434E99CF0497512F58995C1396C28719501EE
LAMBDA = 0x5363AD4CC05C30E0A5261C028812645A122E22EA20816678DF02967C1B23BD72

_GX_LIMBS = int_to_limbs(G_X)
_GY_LIMBS = int_to_limbs(G_Y)
_BETA_LIMBS = int_to_limbs(BETA)
_ONE = int_to_limbs(1)

NBITS = NLIMB * RADIX  # 260 bit positions per scalar (top 4 always zero)
P_WINDOW_BITS = 4
G_WINDOWS = 32
G_WINDOW_BITS = 8


def _col(vec: np.ndarray, like):
    """Constant limb vector -> a column that broadcasts against `like`.

    Routed through `limb_col` so pallas kernels resolve it to a
    constant-table input instead of a captured jnp constant."""
    from .limbs import limb_col

    return limb_col(vec, like)


@named_region("jacobian_double")
def jacobian_double(X, Y, Z):
    """Point doubling, dbl-2009-l for a=0; maps infinity to infinity."""
    A = fe_sqr(X)
    B = fe_sqr(Y)
    C = fe_sqr(B)
    D = fe_mul_small(fe_sub(fe_sqr(fe_add(X, B)), fe_add(A, C)), 2)
    E = fe_mul_small(A, 3)
    F = fe_sqr(E)
    X3 = fe_sub(F, fe_mul_small(D, 2))
    Y3 = fe_sub(fe_mul(E, fe_sub(D, X3)), fe_mul_small(C, 8))
    Z3 = fe_mul_small(fe_mul(Y, Z), 2)  # Z=0 -> Z3=0: infinity preserved
    return X3, Y3, Z3


def _select(mask, a3, b3):
    """Per-lane select between two point triples; mask shape (...,)."""
    m = mask[None]
    return tuple(jnp.where(m, x, y) for x, y in zip(a3, b3, strict=True))


def _inf_like(X):
    zeros = jnp.zeros_like(X)
    ones = jnp.broadcast_to(_col(_ONE, X), X.shape).astype(X.dtype)
    return ones, ones, zeros


def _madd_core(X1, Y1, Z1, x2, y2, inf1):
    """Generic madd-2007-bl formula + exceptional-case masks (the shared
    math of the complete and flagged mixed-add variants; one source so the
    two kernels cannot diverge). Returns (generic_triple, h_zero, r_zero,
    z1_zero, H) where z1_zero follows the inf1 convention (None ->
    computed, False -> statically finite, mask -> as given); H = U2 - X1
    satisfies Z3 = 2*Z1*H (the global-Z ratio callers may record)."""
    Z1Z1 = fe_sqr(Z1)
    U2 = fe_mul(x2, Z1Z1)
    S2 = fe_mul(y2, fe_mul(Z1, Z1Z1))
    H = fe_sub(U2, X1)
    Rsub = fe_sub(S2, Y1)
    if inf1 is None:
        h_zero, r_zero, z1_zero = fe_is_zero_many((H, Rsub, Z1))
    else:
        h_zero, r_zero = fe_is_zero_many((H, Rsub))
        z1_zero = inf1

    HH = fe_sqr(H)
    I = fe_mul_small(HH, 4)
    J = fe_mul(H, I)
    r = fe_mul_small(Rsub, 2)
    V = fe_mul(X1, I)
    X3 = fe_sub(fe_sqr(r), fe_add(J, fe_mul_small(V, 2)))
    Y3 = fe_sub(fe_mul(r, fe_sub(V, X3)), fe_mul_small(fe_mul(Y1, J), 2))
    Z3 = fe_sub(fe_sqr(fe_add(Z1, H)), fe_add(Z1Z1, HH))
    return (X3, Y3, Z3), h_zero, r_zero, z1_zero, H


def _madd_lift(out, X1, x2, y2, z1_zero):
    """Infinite-left-operand case: result is the lifted affine operand."""
    ones = jnp.broadcast_to(_col(_ONE, X1), X1.shape).astype(X1.dtype)
    lift = (jnp.broadcast_to(x2, X1.shape).astype(X1.dtype),
            jnp.broadcast_to(y2, X1.shape).astype(X1.dtype), ones)
    return _select(z1_zero, lift, out)


@named_region("jacobian_madd")
def jacobian_madd_complete(X1, Y1, Z1, x2, y2, inf1=None):
    """Complete mixed addition (X1,Y1,Z1) + (x2,y2), (x2,y2) affine and
    never infinity. Branchless handling of every exceptional case; the
    generic path is madd-2007-bl (the math of `secp256k1_gej_add_ge_var`,
    vectorized and de-branched).

    `inf1`: caller-known infinity status of the left operand — None
    computes the Z1 ≡ 0 field test (legacy), False asserts the operand is
    finite on every live lane, a mask uses it directly. Loop callers that
    track infinity explicitly skip one of the three exact-zero chains.
    """
    out, h_zero, r_zero, z1_zero, _H = _madd_core(X1, Y1, Z1, x2, y2, inf1)
    dbl = jacobian_double(X1, Y1, Z1)
    out = _select(h_zero & r_zero, dbl, out)
    out = _select(h_zero & ~r_zero, _inf_like(X1), out)
    if z1_zero is False:
        # Known-finite left operand: result is infinite only via P+(-P).
        return out + (h_zero & ~r_zero,)
    out = _madd_lift(out, X1, x2, y2, z1_zero)
    if inf1 is None:
        return out
    # inf1 given: also report the result's infinity (affine op is finite).
    return out + (~z1_zero & h_zero & ~r_zero,)


def _add_core(X1, Y1, Z1, X2, Y2, Z2, inf1):
    """Generic add-2007-bl formula + exceptional-case masks (shared by the
    complete and flagged Jacobian-add variants)."""
    Z1Z1 = fe_sqr(Z1)
    Z2Z2 = fe_sqr(Z2)
    U1 = fe_mul(X1, Z2Z2)
    U2 = fe_mul(X2, Z1Z1)
    S1 = fe_mul(Y1, fe_mul(Z2, Z2Z2))
    S2 = fe_mul(Y2, fe_mul(Z1, Z1Z1))
    H = fe_sub(U2, U1)
    Rsub = fe_sub(S2, S1)
    if inf1 is None:
        h_zero, r_zero, z1_zero = fe_is_zero_many((H, Rsub, Z1))
    else:
        h_zero, r_zero = fe_is_zero_many((H, Rsub))
        z1_zero = inf1

    I = fe_sqr(fe_mul_small(H, 2))
    J = fe_mul(H, I)
    r = fe_mul_small(Rsub, 2)
    V = fe_mul(U1, I)
    X3 = fe_sub(fe_sqr(r), fe_add(J, fe_mul_small(V, 2)))
    Y3 = fe_sub(fe_mul(r, fe_sub(V, X3)), fe_mul_small(fe_mul(S1, J), 2))
    Z3 = fe_mul(
        fe_sub(fe_sqr(fe_add(Z1, Z2)), fe_add(Z1Z1, Z2Z2)), H
    )
    return (X3, Y3, Z3), h_zero, r_zero, z1_zero


@named_region("jacobian_add")
def jacobian_add_complete(X1, Y1, Z1, X2, Y2, Z2, inf2, inf1=None):
    """Complete Jacobian+Jacobian addition (add-2007-bl), branchless.

    `inf2` is the caller-known infinity mask for the second operand (table
    entry 0), avoiding a field-level zero test on Z2. `inf1` (optional)
    does the same for the first operand — None computes the Z1 ≡ 0 test."""
    out, h_zero, r_zero, z1_zero = _add_core(X1, Y1, Z1, X2, Y2, Z2, inf1)
    dbl = jacobian_double(X1, Y1, Z1)
    out = _select(h_zero & r_zero, dbl, out)
    out = _select(h_zero & ~r_zero, _inf_like(X1), out)
    out = _select(z1_zero, (X2, Y2, Z2), out)
    out = _select(inf2, (X1, Y1, Z1), out)
    if inf1 is None:
        return out
    # Result infinity: both operands infinite, or finite cancellation.
    out_inf = (z1_zero & inf2) | (~z1_zero & ~inf2 & h_zero & ~r_zero)
    return out + (out_inf,)


def jacobian_madd_flagged(X1, Y1, Z1, x2, y2, inf1):
    """Mixed addition WITHOUT the embedded doubling fallback: the
    equal-points case (h ≡ 0, r ≡ 0) is only FLAGGED (`needs_dbl`), not
    computed — callers defer flagged lanes to the exact host path. Saves
    the jacobian_double (+selects) that `jacobian_madd_complete` pays on
    every call for a case honest traffic never hits (R == ±table point
    requires a crafted scalar collision). Same `_madd_core` math as the
    complete variant. `inf1` is the caller-tracked infinity mask of the
    left operand (or False when statically finite). Returns
    (X, Y, Z, out_inf, needs_dbl)."""
    out, h_zero, r_zero, z1_zero, _H = _madd_core(X1, Y1, Z1, x2, y2, inf1)
    out = _select(h_zero & ~r_zero, _inf_like(X1), out)
    if z1_zero is False:
        # Caller-asserted finite left operand: no lift select needed.
        return out + (h_zero & ~r_zero, h_zero & r_zero)
    out = _madd_lift(out, X1, x2, y2, z1_zero)
    out_inf = ~z1_zero & h_zero & ~r_zero
    needs_dbl = ~z1_zero & h_zero & r_zero
    return out + (out_inf, needs_dbl)


def jacobian_madd_flagged_ratio(X1, Y1, Z1, x2, y2, inf1=False):
    """`jacobian_madd_flagged` that also returns the Z-ratio
    ``Z3/Z1 = 2H`` (madd-2007-bl: Z3 = (Z1+H)^2 - Z1Z1 - HH = 2*Z1*H).
    The per-lane table build records these ratios so the whole table can
    be renormalized to the LAST entry's Z with multiplications only — the
    reference's effective-affine/global-Z trick
    (`secp256k1/src/ecmult_impl.h:61-136` odd-multiples table +
    `secp256k1_ge_table_set_globalz`) — no field inversion. Exceptional
    lanes (h ≡ 0) produce a meaningless ratio; callers defer those lanes
    to the host via the needs flag, so the garbage never reaches a
    verdict. Returns (X, Y, Z, out_inf, needs_dbl, ratio)."""
    out, h_zero, r_zero, z1_zero, H = _madd_core(X1, Y1, Z1, x2, y2, inf1)
    ratio = fe_mul_small(H, 2)
    out = _select(h_zero & ~r_zero, _inf_like(X1), out)
    if z1_zero is False:
        return out + (h_zero & ~r_zero, h_zero & r_zero, ratio)
    out = _madd_lift(out, X1, x2, y2, z1_zero)
    out_inf = ~z1_zero & h_zero & ~r_zero
    needs_dbl = ~z1_zero & h_zero & r_zero
    return out + (out_inf, needs_dbl, ratio)


def jacobian_add_flagged(X1, Y1, Z1, X2, Y2, Z2, inf2, inf1):
    """Jacobian+Jacobian addition without the doubling fallback (see
    jacobian_madd_flagged); same `_add_core` math as the complete variant.
    `inf2`/`inf1`: caller-tracked infinity masks. Returns
    (X, Y, Z, out_inf, needs_dbl)."""
    out, h_zero, r_zero, z1_zero = _add_core(X1, Y1, Z1, X2, Y2, Z2, inf1)
    out = _select(h_zero & ~r_zero, _inf_like(X1), out)
    out = _select(z1_zero, (X2, Y2, Z2), out)
    out = _select(inf2, (X1, Y1, Z1), out)
    out_inf = (z1_zero & inf2) | (~z1_zero & ~inf2 & h_zero & ~r_zero)
    needs_dbl = ~z1_zero & ~inf2 & h_zero & r_zero
    return out + (out_inf, needs_dbl)


def scalar_bits(limbs):
    """(20, ...) scalar limbs -> (260, ...) bits, LSB first."""
    shifts = jnp.arange(RADIX, dtype=jnp.int32).reshape(
        (1, RADIX) + (1,) * (limbs.ndim - 1)
    )
    bits = (limbs[:, None] >> shifts) & 1
    return bits.reshape((NBITS,) + limbs.shape[1:])


def _digits(limbs, width: int, count: int):
    """(20, ...) scalar limbs -> (count, ...) window digits, LSB first."""
    bits = scalar_bits(limbs)[:256]
    b = bits.reshape((count, width) + limbs.shape[1:])
    weights = jnp.asarray([1 << i for i in range(width)], dtype=jnp.int32)
    weights = weights.reshape((1, width) + (1,) * (limbs.ndim - 1))
    return jnp.sum(b * weights, axis=1)


_GTABLE = None


def _g_table():
    """(32, 255, 20) x2 affine G window table. Cached as numpy (host) so no
    traced value ever leaks into the cache; jnp conversion happens at the
    use site inside whatever trace is active."""
    global _GTABLE
    if _GTABLE is None:
        path = os.path.join(os.path.dirname(__file__), "_gtable8.npz")
        if os.path.exists(path):
            data = np.load(path)
            gx, gy = data["gx"], data["gy"]
        else:  # slow fallback: regenerate (deterministic)
            from .gen_gtable import build_tables

            gx, gy = build_tables()
        _GTABLE = (np.asarray(gx), np.asarray(gy))
    return jnp.asarray(_GTABLE[0]), jnp.asarray(_GTABLE[1])


def _fixed_base_mult(a_digits):
    """RG = a·G from 8-bit window digits (32, B): 32 complete madds, no
    doublings. The per-window row select is an exact f32 matmul
    (one-hot (255, B) against the (255, 20) window table): 13-bit limbs
    are exact in f32, and the contraction feeds the MXU instead of
    per-lane gathers."""
    gx_t, gy_t = _g_table()
    gx_f = gx_t.astype(jnp.float32)  # (32, 255, 20)
    gy_f = gy_t.astype(jnp.float32)
    k255 = jnp.arange(1, 256, dtype=jnp.int32)[:, None]  # (255, 1)

    def body(i, carry):
        X, Y, Z, rg_inf = carry
        da = a_digits[i]  # (B,)
        oh = (da[None, :] == k255).astype(jnp.float32)  # (255, B)
        gxw = lax.dynamic_index_in_dim(gx_f, i, axis=0, keepdims=False)
        gyw = lax.dynamic_index_in_dim(gy_f, i, axis=0, keepdims=False)
        # Precision.HIGHEST is load-bearing: the TPU MXU lowers default-
        # precision f32 dots to bfloat16 passes (8-bit mantissa), which
        # silently truncates 13-bit limbs.
        selx = jnp.dot(gxw.T, oh, preferred_element_type=jnp.float32,
                       precision=lax.Precision.HIGHEST)
        sely = jnp.dot(gyw.T, oh, preferred_element_type=jnp.float32,
                       precision=lax.Precision.HIGHEST)
        selx = selx.astype(jnp.int32)  # (20, B), exact
        sely = sely.astype(jnp.int32)
        Xa, Ya, Za, inf_a = jacobian_madd_complete(
            X, Y, Z, selx, sely, inf1=rg_inf
        )
        app = da > 0
        out = _select(app, (Xa, Ya, Za), (X, Y, Z))
        return out + (jnp.where(app, inf_a, rg_inf),)

    zeros = jnp.zeros_like(a_digits[0])
    inf = _inf_like(zeros[None].repeat(NLIMB, axis=0))
    all_inf = jnp.ones(a_digits.shape[1:], dtype=bool)
    X, Y, Z, rg_inf = lax.fori_loop(0, G_WINDOWS, body, inf + (all_inf,))
    return (X, Y, Z), rg_inf


def _p_table(px, py):
    """Per-lane Jacobian table T[k] = k·P, k = 0..15, via a 14-step scan
    (T[0] = infinity, T[1] = P). Returns (16, 20, B) coord stacks."""
    ones = jnp.broadcast_to(_col(_ONE, px), px.shape).astype(px.dtype)
    inf = _inf_like(px)

    def step(carry, _):
        # carry = k·P, k >= 1 — never infinity for on-curve P (order n
        # >> 16), so the Z1 exact test is skipped (inf1=False).
        *nxt, _cancel = jacobian_madd_complete(*carry, px, py, inf1=False)
        nxt = tuple(nxt)
        return nxt, nxt

    _, tail = lax.scan(step, (px, py, ones), None, length=14)
    TX = jnp.concatenate([inf[0][None], px[None], tail[0]], axis=0)
    TY = jnp.concatenate([inf[1][None], py[None], tail[1]], axis=0)
    TZ = jnp.concatenate([inf[2][None], ones[None], tail[2]], axis=0)
    return TX, TY, TZ


GLV_WINDOWS = 32  # 4-bit windows over the 128-bit split halves


def _digits128(limbs10, count: int = GLV_WINDOWS, width: int = P_WINDOW_BITS):
    """(10, ...) limb vector of a < 2^130 value -> (count, ...) 4-bit
    window digits, LSB first (only bits 0..count*width-1 are consumed)."""
    shifts = jnp.arange(RADIX, dtype=jnp.int32).reshape(
        (1, RADIX) + (1,) * (limbs10.ndim - 1)
    )
    bits = ((limbs10[:, None] >> shifts) & 1).reshape(
        (10 * RADIX,) + limbs10.shape[1:]
    )[: count * width]
    b = bits.reshape((count, width) + limbs10.shape[1:])
    weights = jnp.asarray([1 << i for i in range(width)], dtype=jnp.int32)
    weights = weights.reshape((1, width) + (1,) * (limbs10.ndim - 1))
    return jnp.sum(b * weights, axis=1)


@named_region("scalar_mult")
def double_scalar_mult_glv(a, db1, db2, neg1, neg2, px, py):
    """R = a·G + (±b1 + lambda·(±b2))·P with the GLV-split schedule.

    `a`: (20, ...) scalar limbs (reduced mod n). `db1`, `db2`:
    (32, ...) 4-bit window digits of |b1|, |b2| < 2^128. `neg1`, `neg2`:
    (...,) bool — negate the respective half (the split yields signed
    halves; -P = (x, -y)). `px`, `py`: affine P, never infinity.

    Schedule per lane: 14 madds (shared table) + 32x(4 doublings + 2
    complete adds + 1 beta-mul + y-negates) + 32 G madds + join — the
    endomorphism halves the 256 doublings of the non-GLV ladder
    (reference precedent: secp256k1_scalar_split_lambda + ecmult's
    wnaf_lam track, ecmult_impl.h:446-559 with USE_ENDOMORPHISM).
    """
    digits_a = _digits(a, G_WINDOW_BITS, G_WINDOWS)

    TX, TY, TZ = _p_table(px, py)
    beta = jnp.broadcast_to(_col(_BETA_LIMBS, px), px.shape).astype(px.dtype)
    k16 = jnp.arange(16, dtype=jnp.int32).reshape((16,) + (1,) * px.ndim)
    n1 = neg1[None]
    n2 = neg2[None]

    def body(i, carry):
        # R's infinity is tracked explicitly across the loop: the adds
        # skip the Z1 ≡ 0 exact test and report the result's status.
        X, Y, Z, r_inf = carry
        R = (X, Y, Z)
        w = GLV_WINDOWS - 1 - i
        R = jacobian_double(*R)  # doublings preserve infinity
        R = jacobian_double(*R)
        R = jacobian_double(*R)
        R = jacobian_double(*R)
        d1 = db1[w]
        oh = (d1[None] == k16).astype(jnp.int32)
        sx = jnp.sum(TX * oh, axis=0)
        sy = jnp.sum(TY * oh, axis=0)
        sz = jnp.sum(TZ * oh, axis=0)
        sy = jnp.where(n1, fe_sub(jnp.zeros_like(sy), sy), sy)
        *R, r_inf = jacobian_add_complete(*R, sx, sy, sz, d1 == 0, inf1=r_inf)
        d2 = db2[w]
        oh = (d2[None] == k16).astype(jnp.int32)
        sx = fe_mul(jnp.sum(TX * oh, axis=0), beta)  # lambda*(x,y)=(bx,y)
        sy = jnp.sum(TY * oh, axis=0)
        sz = jnp.sum(TZ * oh, axis=0)
        sy = jnp.where(n2, fe_sub(jnp.zeros_like(sy), sy), sy)
        X, Y, Z, r_inf = jacobian_add_complete(*R, sx, sy, sz, d2 == 0, inf1=r_inf)
        return X, Y, Z, r_inf

    all_inf = jnp.ones(px.shape[1:], dtype=bool)
    R = lax.fori_loop(0, GLV_WINDOWS, body, _inf_like(px) + (all_inf,))
    X, Y, Z, r_inf = R
    RG, rg_inf = _fixed_base_mult(digits_a)
    X, Y, Z, out_inf = jacobian_add_complete(
        X, Y, Z, *RG, rg_inf, inf1=r_inf
    )
    return X, Y, Z, out_inf


@named_region("to_affine")
def jacobian_to_affine(X, Y, Z, inf=None):
    """(X, Y, Z) -> (x, y, is_infinity) with x, y canonical in [0, p).

    (20, B) batches share one Montgomery-trick inversion across the batch
    (fe_batch_inv, ~4 muls/lane); other shapes fall back to per-lane
    Fermat. Infinity lanes return x = y = 0. `inf` (optional) is a
    caller-tracked infinity mask, replacing the Z ≡ 0 exact test."""
    if inf is None:
        inf = fe_is_zero(Z)
    if Z.ndim == 2:
        zi = fe_batch_inv(Z, inf)
    else:
        zi = fe_inv(Z)
    zi2 = fe_sqr(zi)
    x = fe_canon(fe_mul(X, zi2))
    y = fe_canon(fe_mul(Y, fe_mul(zi2, zi)))
    return x, y, inf
