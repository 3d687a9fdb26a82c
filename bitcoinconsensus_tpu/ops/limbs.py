"""Batched 256-bit field arithmetic mod p (secp256k1) for TPU — limb-major.

Design (TPU-first, not a port). A field element is 20 little-endian limbs
in radix 2^13, dtype int32, **limb axis first**: shape ``(20, ...)`` with
the batch in the trailing (lane) axes. Two hardware facts drive the layout
and the carry scheme:

- The VPU operates on (8, 128) registers: the *last* dimension maps to
  128 lanes, the one before it to 8 sublanes. Batch-last means every
  elementwise op runs at full lane occupancy. Every routine here takes any
  trailing shape: on a 2-D ``(20, B)`` batch (the XLA path) the 20-limb
  axis lies on the sublanes; the Pallas kernel folds its batch to
  ``(20, S, 128)``, where the limb axis is an untiled leading axis, a limb
  is a whole register, and the shifts, pads and slices along the limbs
  below are plain indexing (`ops/pallas_kernel.py`). (The transposed
  layout — limbs last — wastes 108/128 lanes on every op.)
- There is no 64-bit multiplier. A 13x13-bit product is < 2^26 and a
  20-term schoolbook column sums to < 2^31, so every intermediate of a
  256-bit multiply fits a signed int32 lane. The reference proves the
  same idea at different widths (its 32-bit build uses 10x26 field limbs,
  `secp256k1/src/field_10x26_impl.h`); we shrink the radix so whole
  products fit one lane and vectorize over the batch instead of time.

Carry handling is *parallel only* — there are no sequential per-limb
chains anywhere in the hot path:

- `_pass` ships every limb's carry one position up simultaneously and
  wraps the carry out of limb 19 back into limbs 0..2 via
  2^260 ≡ 16C (mod p), C = 2^32 + 977 (16C = 2^36 + 15632, the 3-limb
  constant [7440, 1, 1024] in radix 2^13) — the pseudo-Mersenne
  wrap-around pass.
- Exactness (canonicalization, zero tests) uses a Kogge-Stone
  carry-lookahead: generate/propagate per limb, log2(20) combine steps,
  all whole-array ops.

Alongside the traced arrays every routine tracks static Python-int
per-limb upper bounds, so pass counts and fold rounds are fixed at trace
time and int32 overflow-freedom is checked by construction.

Representation invariant ("weak"): per-limb bounds `W2` (the fixpoint of
the wrap-around pass): limb 0 ≤ 2^13-1+7440, limb 1 ≤ 2^13+1,
limb 2 ≤ 2^13+1024, limbs 3..19 ≤ 2^13. All public ops accept and return
weak elements; `fe_canon` produces the unique representative in [0, p).

Spec source: the reference's field semantics (`secp256k1/src/field_*_impl.h`)
— behavior only; layout and algorithms here are TPU designs.
"""

from __future__ import annotations

import threading
from typing import List, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from .regions import named_region

__all__ = [
    "NLIMB",
    "RADIX",
    "MASK",
    "P_INT",
    "W2",
    "int_to_limbs",
    "limbs_to_int",
    "fe_add",
    "fe_sub",
    "fe_mul",
    "fe_sqr",
    "fe_mul_small",
    "fe_canon",
    "fe_is_zero",
    "fe_is_zero_many",
    "fe_eq",
    "fe_inv",
    "fe_batch_inv",
    "fe_pow_const",
    "fe_sqrt",
    "ints_to_limbs_batch",
]

NLIMB = 20
RADIX = 13
MASK = (1 << RADIX) - 1

P_INT = 2**256 - 2**32 - 977
_C = 2**32 + 977  # 2^256 mod p
# 2^260 mod p = 16C = 2^36 + 15632 -> radix-2^13 limbs [7440, 1, 1024].
_FOLD260 = (7440, 1, 1024)

# Weak bounds: fixpoint of the wrap-around pass (see _pass). With carries
# <= 1 in steady state: limb0 <= MASK + 1*7440, limb1 <= MASK + 1 + 1,
# limb2 <= MASK + 1 + 1*1024, others <= MASK + 1.
W2 = [MASK + 7440, MASK + 2, MASK + 1025] + [MASK + 1] * (NLIMB - 3)

# Mul safety: every schoolbook column sum must fit int32.
for _k in range(2 * NLIMB - 1):
    _col = sum(
        W2[_i] * W2[_k - _i]
        for _i in range(max(0, _k - NLIMB + 1), min(NLIMB, _k + 1))
    )
    assert _col < 2**31, (_k, _col)
# Value bound: weak values are < 2^261 (single-carry wrap in _exact260).
assert sum(w << (RADIX * i) for i, w in enumerate(W2)) < 2**261


def int_to_limbs(x: int, n: int = NLIMB) -> np.ndarray:
    """Host helper: Python int -> little-endian radix-2^13 limb vector."""
    out = np.zeros(n, dtype=np.int32)
    for i in range(n):
        out[i] = x & MASK
        x >>= RADIX
    if x:
        raise ValueError("value does not fit limb vector")
    return out


def limbs_to_int(limbs) -> int:
    """Host helper: limb vector (FIRST axis) -> Python int."""
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(v) << (RADIX * i) for i, v in enumerate(arr))


def ints_to_limbs_batch(vals) -> np.ndarray:
    """Vectorized host packing: list of ints (< 2^257) -> (n, 20) int32.

    Row-major (one row per value) because that is the natural host order;
    the device kernel transposes once at entry to the limb-major layout.
    """
    raw = b"".join(v.to_bytes(33, "little") for v in vals)
    nb = np.frombuffer(raw, dtype=np.uint8).reshape(-1, 33).astype(np.int64)
    limbs = np.empty((len(vals), NLIMB), dtype=np.int32)
    for i in range(NLIMB):
        bitpos = RADIX * i
        k, sh = bitpos >> 3, bitpos & 7
        window = nb[:, k] | (nb[:, k + 1] << 8) | (nb[:, k + 2] << 16)
        limbs[:, i] = (window >> sh) & MASK
    return limbs


_P_LIMBS = int_to_limbs(P_INT)

Bounds = List[int]

# Constant provider hook. Pallas kernels cannot capture array constants
# (they must arrive as kernel inputs), so the pallas wrapper installs a
# provider that resolves well-known (20,) limb vectors to rows of a
# constant-table input; the default inlines them as jnp constants (XLA).
# Thread-LOCAL: tracing runs on the calling thread, and a concurrent
# XLA trace on another thread must not see a Pallas trace's provider
# (concurrent verify_batch is part of the documented thread contract).
_CONST_PROVIDER = threading.local()


def limb_const(arr: np.ndarray):
    provider = getattr(_CONST_PROVIDER, "fn", None)
    if provider is not None:
        out = provider(arr)
        if out is not None:
            return out
    return jnp.asarray(arr)


def limb_col(arr: np.ndarray, like):
    """A well-known limb vector as a column that broadcasts against the
    element `like`: (20, 1, ..., 1) — or the provider's own array where
    that already has `like`'s rank (the Pallas kernel's constant table
    holds every limb spread over a whole tile, so a use costs a load and
    no broadcast; `fe_is_zero_many` widens the lanes k-fold, and the tile
    repeats k times beside itself)."""
    c = limb_const(arr)
    if c.ndim != like.ndim:
        return c.reshape((NLIMB,) + (1,) * (like.ndim - 1))
    k = like.shape[-1] // c.shape[-1]
    return c if k == 1 else jnp.concatenate([c] * k, axis=-1)


def set_const_provider(fn):
    """Install (or clear, with None) this thread's provider; returns the
    previous one so callers can restore it (used by ops/pallas_kernel.py)."""
    prev = getattr(_CONST_PROVIDER, "fn", None)
    _CONST_PROVIDER.fn = fn
    return prev


def bytes_to_limbs(u8, nlimb: int = NLIMB):
    """Device-side unpack: (..., K) uint8 little-endian values -> limb-major
    (nlimb, ...) int32 (K*8 <= nlimb*RADIX; default 32 bytes -> 20 limbs).

    Transfers over the host->device link are the scarce resource (32 bytes
    per field instead of 80 bytes of pre-split limbs); the unpack is a
    handful of static gathers + shifts, so it runs where compute is cheap.
    """
    nbytes = u8.shape[-1]
    assert nbytes * 8 <= nlimb * RADIX
    x = u8.astype(jnp.int32)
    # Top limb windows may span past the last byte: zero-pad.
    pad_n = (RADIX * (nlimb - 1) >> 3) + 3 - nbytes
    if pad_n > 0:
        pad = jnp.zeros(x.shape[:-1] + (pad_n,), dtype=x.dtype)
        x = jnp.concatenate([x, pad], axis=-1)
    limbs = []
    for i in range(nlimb):
        bitpos = RADIX * i
        k, sh = bitpos >> 3, bitpos & 7
        window = x[..., k] | (x[..., k + 1] << 8) | (x[..., k + 2] << 16)
        limbs.append((window >> sh) & MASK)
    return jnp.stack(limbs, axis=0)


def _zeros_rows(x, n: int):
    return jnp.zeros((n,) + x.shape[1:], dtype=x.dtype)


def _cat_rows(parts):
    """Concatenate along the limb axis, dropping zero-row operands —
    Mosaic (pallas) rejects zero-sized vectors that XLA tolerates."""
    parts = [p for p in parts if p.shape[0] != 0]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _pad_rows(x, lo: int, hi: int):
    """x padded with `lo` zero rows below and `hi` above, as ONE lax.pad
    op — the convolutions pad every row product into the output width,
    and materializing the zeros as separate arrays + concatenate doubled
    the kernel's data-movement op count."""
    if lo == 0 and hi == 0:
        return x
    from jax import lax

    cfg = [(lo, hi, 0)] + [(0, 0, 0)] * (x.ndim - 1)
    return lax.pad(x, jnp.zeros((), dtype=x.dtype), cfg)


def _pass(x, bounds: Bounds) -> Tuple[jnp.ndarray, Bounds]:
    """One parallel carry pass along the limb axis.

    At exactly NLIMB limbs the carry out of limb 19 wraps into limbs 0..2
    via 16C (value changes by a multiple of p only). With more limbs the
    top carry appends a column (folded later by _fold_high).
    """
    assert all(0 <= b < 2**31 for b in bounds)
    n = x.shape[0]
    c = x >> RADIX
    kept = x & MASK
    out = kept + _pad_rows(c[:-1], 1, 0)
    cb = [b >> RADIX for b in bounds]
    b2 = [min(bounds[0], MASK)] + [
        min(bounds[i], MASK) + cb[i - 1] for i in range(1, n)
    ]
    top = c[n - 1]
    if cb[-1] == 0:
        return out, b2
    if n == NLIMB:
        wrap = jnp.stack(
            [top * _FOLD260[0], top * _FOLD260[1], top * _FOLD260[2]], axis=0
        )
        out = out + _pad_rows(wrap, 0, NLIMB - 3)
        for j, f in enumerate(_FOLD260):
            b2[j] += cb[-1] * f
            assert b2[j] < 2**31
        return out, b2
    out = jnp.concatenate([out, top[None]], axis=0)
    b2.append(cb[-1])
    return out, b2


def _fold_high(x, bounds: Bounds) -> Tuple[jnp.ndarray, Bounds]:
    """Fold limbs at positions >= NLIMB down via 2^260 ≡ 16C."""
    n_hi = x.shape[0] - NLIMB
    assert n_hi > 0
    out_len = max(NLIMB, n_hi + len(_FOLD260) - 1)
    lo, hi = x[:NLIMB], x[NLIMB:]
    pad = out_len - NLIMB
    acc = _pad_rows(lo, 0, pad) if pad else lo
    b2 = bounds[:NLIMB] + [0] * pad
    for j, f in enumerate(_FOLD260):
        acc = acc + _pad_rows(hi * f, j, out_len - j - n_hi)
        for i in range(n_hi):
            b2[i + j] += bounds[NLIMB + i] * f
            assert b2[i + j] < 2**31
    return acc, b2


def _settled(bounds: Bounds) -> bool:
    return len(bounds) == NLIMB and all(b <= w for b, w in zip(bounds, W2, strict=True))


def _settle(x, bounds: Bounds):
    """Drive any nonnegative limb vector into weak (W2-bounded) form.

    Control flow depends only on the static bounds: the emitted op
    sequence is fixed at trace time. Pure parallel passes + folds — no
    sequential per-limb chains.
    """
    assert x.shape[0] == len(bounds)
    guard = 0
    while not _settled(bounds):
        guard += 1
        assert guard < 24, "settle failed to converge (static bounds bug)"
        if len(bounds) > NLIMB and all(
            b * _FOLD260[0] < 2**30 for b in bounds[NLIMB:]
        ):
            x, bounds = _fold_high(x, bounds)
        else:
            x, bounds = _pass(x, bounds)
    return x


@named_region("fe_add")
def fe_add(a, b):
    """a + b mod p (weak in, weak out)."""
    return _settle(a + b, [2 * w for w in W2])


_SUB_K = 32  # bias = 32p, encoded with every limb >= W2 (see below)


def _sub_bias_limbs() -> np.ndarray:
    """Encode 32p in 20 limbs with limb i >= W2[i], so a + bias - b is
    nonnegative per limb for any weak a, b (bias value ≡ 0 mod p)."""
    d = [int(v) for v in int_to_limbs(_SUB_K * P_INT, 21)]
    # Merge the top limb down (32p < 2^261 so limb 20 is tiny).
    d[19] += d[20] << RADIX
    d = d[:20]
    for i in range(NLIMB - 1):
        while d[i] < W2[i]:
            d[i] += 1 << RADIX
            d[i + 1] -= 1
    assert all(d[i] >= W2[i] for i in range(NLIMB)), d
    assert all(d[i] + W2[i] < 2**31 for i in range(NLIMB))
    assert sum(v << (RADIX * i) for i, v in enumerate(d)) == _SUB_K * P_INT
    return np.asarray(d, dtype=np.int32)


_SUB_BIAS = _sub_bias_limbs()
_SUB_BOUNDS = [int(d) + w for d, w in zip(_SUB_BIAS, W2, strict=True)]


@named_region("fe_sub")
def fe_sub(a, b):
    """a - b mod p (weak in/out): a + 32p(in >=W2-limb form) - b >= 0."""
    bias = limb_col(_SUB_BIAS, a)
    return _settle(a + bias - b, list(_SUB_BOUNDS))


def fe_mul_small(a, k: int):
    """a * k mod p for a small static k (k * W2[0] must fit int32)."""
    assert 0 < k and k * W2[0] < 2**31
    return _settle(a * k, [w * k for w in W2])


def _conv_rows(a, b, bw: Bounds, aw: Bounds, nl: int = NLIMB):
    """Schoolbook convolution: out[k] = sum_{i+j=k} a[i]*b[j] over nl-limb
    operands."""
    out_len = 2 * nl - 1
    acc = None
    bounds = [0] * out_len
    for i in range(nl):
        row = a[i] * b  # (nl, ...) scaled by one limb of a
        padded = _pad_rows(row, i, out_len - i - nl)
        acc = padded if acc is None else acc + padded
        for j in range(nl):
            bounds[i + j] += aw[i] * bw[j]
    assert all(bv < 2**31 for bv in bounds)
    return acc, bounds


def _conv_rows_kara(a, b, aw: Bounds, bw: Bounds, nl: int):
    """One Karatsuba level over an nl-limb convolution whose TRUE weights
    are aw/bw (all columns of all three sub-convolutions provably below
    2^31 — only usable for real-weight operands, not for the wrapping
    sum-convolution of the outer level). nl must be even."""
    h = nl // 2
    alo, ahi = a[:h], a[h:nl]
    blo, bhi = b[:h], b[h:nl]
    z0, b0 = _conv_rows(alo, blo, bw[:h], aw[:h], nl=h)
    z2, b2 = _conv_rows(ahi, bhi, bw[h:nl], aw[h:nl], nl=h)
    S = None
    asum, bsum = alo + ahi, blo + bhi
    for i in range(h):
        row = asum[i] * bsum
        padded = _pad_rows(row, i, h - 1 - i)
        S = padded if S is None else S + padded
    z1b = _cross_bounds(aw, bw, h)
    return _kara_combine(z0, b0, z2, b2, S, z1b, h, 2 * nl - 1)


def _sqr_rows(a, aw: Bounds, nl: int):
    """Squaring convolution over nl limbs: diagonal once + doubled cross
    terms — ~45% fewer multiplies than the generic convolution."""
    out_len = 2 * nl - 1
    acc = None
    bounds = [0] * out_len
    a2 = a * 2
    for i in range(nl):
        hi = nl - i - 1
        diag = a[i : i + 1] * a[i : i + 1]
        # hi == 0 (last limb): the cross-term slice would be zero-size,
        # which Mosaic rejects — emit the diagonal alone.
        row = _cat_rows([diag, a[i] * a2[i + 1 : nl]]) if hi else diag
        padded = _pad_rows(row, 2 * i, out_len - 2 * i - 1 - hi)
        acc = padded if acc is None else acc + padded
        bounds[2 * i] += aw[i] * aw[i]
        for j in range(i + 1, nl):
            bounds[i + j] += 2 * aw[i] * aw[j]
    assert all(bv < 2**31 for bv in bounds)
    return acc, bounds


# Karatsuba split: 20 = 10 + 10. One level replaces the 400-product
# schoolbook convolution with three 100-product half-convolutions plus
# O(n) combines (~25% fewer per-lane ops where the kernel spends most of
# its time). Exactness under int32 WRAPPING: XLA int32 add/mul are
# two's-complement (exact mod 2^32); the sum-convolution S may exceed
# 2^31 and wrap, but z1 = S - z0 - z2 is computed mod 2^32 and its TRUE
# value (the cross convolution, statically bounded below 2^31 by the
# asserted bounds) is therefore recovered exactly. The assembled columns
# are sums of sub-convolution TAILS with HEADS, so their true bounds stay
# below 2^31 (asserted), keeping _settle's nonnegative-value semantics.
_KARA_LO = 10


def _cross_bounds(wa: Bounds, wb: Bounds, h: int) -> Bounds:
    """True per-column bounds of the CROSS convolution lo*hi + hi*lo —
    what z1 = S - z0 - z2 recovers exactly despite S wrapping."""
    z1b = [0] * (2 * h - 1)
    for i in range(h):
        for j in range(h):
            z1b[i + j] += wa[i] * wb[h + j] + wa[h + i] * wb[j]
    return z1b


def _kara_combine(z0, b0, z2, b2, S, z1_true_bounds, h: int, out_len: int):
    """Assemble z0 + (S - z0 - z2)<<(RADIX*h) + z2<<(RADIX*2h) with static
    bounds; returns (acc, bounds) shaped like an out_len-column
    convolution. Shared by both Karatsuba levels (fe_mul/fe_sqr outer,
    _conv_rows_kara inner) so the overflow bookkeeping lives once."""
    z1 = S - z0 - z2  # exact mod 2^32; true value bounded by z1_true_bounds
    for tb in z1_true_bounds:
        assert 0 <= tb < 2**31
    acc = _pad_rows(z0, 0, out_len - (2 * h - 1))
    acc = acc + _pad_rows(z1, h, out_len - h - (2 * h - 1))
    acc = acc + _pad_rows(z2, 2 * h, out_len - 2 * h - (2 * h - 1))
    bounds = [0] * out_len
    for k in range(2 * h - 1):
        bounds[k] += b0[k]
        bounds[k + h] += z1_true_bounds[k]
        bounds[k + 2 * h] += b2[k]
    assert all(bv < 2**31 for bv in bounds)
    return acc, bounds


@named_region("fe_mul")
def fe_mul(a, b):
    """a * b mod p (weak in, weak out): one-level Karatsuba over the limb
    convolution + parallel carry passes — the per-lane unit the whole
    verify kernel reduces to."""
    h = _KARA_LO
    alo, ahi = a[:h], a[h:]
    blo, bhi = b[:h], b[h:]
    wlo, whi = W2[:h], W2[h:]
    # The real-weight halves take a second Karatsuba level (their columns
    # stay provably below 2^31); the wrapping sum-convolution cannot.
    z0, b0 = _conv_rows_kara(alo, blo, wlo, wlo, nl=h)
    z2, b2 = _conv_rows_kara(ahi, bhi, whi, whi, nl=h)
    asum, bsum = alo + ahi, blo + bhi
    # The sum-convolution is inlined (NOT via _conv_rows) because its
    # columns may exceed 2^31 and wrap — which is exact mod 2^32, but
    # would trip _conv_rows's nonnegative static-bound assertion.
    S = None
    for i in range(h):
        padded = _pad_rows(asum[i] * bsum, i, h - 1 - i)
        S = padded if S is None else S + padded
    z1b = _cross_bounds(W2, W2, h)
    acc, bounds = _kara_combine(z0, b0, z2, b2, S, z1b, h, 2 * NLIMB - 1)
    return _settle(acc, bounds)


@named_region("fe_sqr")
def fe_sqr(a):
    """a^2 mod p: Karatsuba over the squaring convolution (three half
    squares; diagonals once, cross terms doubled)."""
    h = _KARA_LO
    alo, ahi = a[:h], a[h:]
    wlo, whi = W2[:h], W2[h:]
    z0, b0 = _sqr_rows(alo, wlo, h)
    z2, b2 = _sqr_rows(ahi, whi, h)
    asum = alo + ahi
    S = None
    a2 = asum * 2
    for i in range(h):
        hi = h - i - 1
        diag = asum[i : i + 1] * asum[i : i + 1]
        row = _cat_rows([diag, asum[i] * a2[i + 1 : h]]) if hi else diag
        padded = _pad_rows(row, 2 * i, 2 * h - 1 - 2 * i - 1 - hi)
        S = padded if S is None else S + padded
    z1b = _cross_bounds(W2, W2, h)
    acc, bounds = _kara_combine(z0, b0, z2, b2, S, z1b, h, 2 * NLIMB - 1)
    return _settle(acc, bounds)


# ---------------------------------------------------------------------------
# Exactness: Kogge-Stone carry lookahead (all whole-array ops).

_KS_MAX = (1 << (RADIX + 1)) - 2  # per-limb cap for single-bit carries


def _ks_exact(x):
    """Exact carry propagation for limbs <= _KS_MAX: returns (exact 13-bit
    limbs, carry-out of limb 19 in {0,1}). Kogge-Stone over the limb axis:
    g=generate, pr=propagate, log2(20)=5 combine steps."""
    g = (x > MASK).astype(jnp.int32)
    pr = (x == MASK).astype(jnp.int32)
    d = 1
    while d < NLIMB:
        gs = jnp.concatenate([_zeros_rows(g, d), g[:-d]], axis=0)
        ps = jnp.concatenate([_zeros_rows(pr, d), pr[:-d]], axis=0)
        g = g | (pr & gs)
        pr = pr & ps
        d *= 2
    cin = jnp.concatenate([_zeros_rows(g, 1), g[:-1]], axis=0)
    exact = (x + cin) & MASK
    return exact, g[NLIMB - 1]


def _exact_lt_2p(x, bounds: Bounds):
    """Weak-ish x -> exact 13-bit limbs of a value v ≡ x (mod p), v < 2p.

    Steps: settle into KS range -> KS (value < 2^261 so carry-out <= 1)
    -> fold carry-out and bits 256..259 via C multiples -> second KS.
    """
    while len(bounds) > NLIMB or any(b > _KS_MAX for b in bounds):
        if len(bounds) > NLIMB:
            x, bounds = _fold_high(x, bounds)
        else:
            x, bounds = _pass(x, bounds)
    assert sum(b << (RADIX * i) for i, b in enumerate(bounds)) < 2**261
    e, cout = _ks_exact(x)
    # v1 = e + cout*2^260; fold cout*2^260 ≡ cout*16C and the top 4 bits
    # of limb 19 (2^256..2^259) ≡ hi4*C = hi4*(977 + 64*2^26).
    hi4 = e[NLIMB - 1] >> 9
    top = e[NLIMB - 1] & 0x1FF
    f0 = e[0] + cout * _FOLD260[0] + hi4 * 977
    f1 = e[1] + cout * _FOLD260[1]
    f2 = e[2] + cout * _FOLD260[2] + hi4 * 64
    # f0 <= MASK+7440+14655, beyond the single-bit-carry KS range: absorb
    # its carry into f1 locally (one shift+add, still fully parallel).
    f1 = f1 + (f0 >> RADIX)
    f0 = f0 & MASK
    x2 = jnp.concatenate(
        [jnp.stack([f0, f1, f2], axis=0), e[3 : NLIMB - 1], top[None]], axis=0
    )
    # Bounds after absorb: f0<=MASK, f1<=MASK+1+3, f2<=MASK+1024+960.
    assert MASK + _FOLD260[1] + (MASK + _FOLD260[0] + 15 * 977) // (MASK + 1) <= _KS_MAX
    assert MASK + _FOLD260[2] + 15 * 64 <= _KS_MAX
    e2, cout2 = _ks_exact(x2)
    # v2 = (e - hi4*2^256) + hi4*C + cout*16C < 2^256 + 31C < 2p, and
    # < 2^260, so cout2 is structurally 0; e2 is exact.
    del cout2
    return e2


@named_region("fe_canon")
def fe_canon(a, bounds: Bounds = None):
    """Weak -> canonical representative in [0, p), exact 13-bit limbs."""
    e = _exact_lt_2p(a, list(W2) if bounds is None else list(bounds))
    # One conditional subtract-p via borrow lookahead: d = e - p limbwise;
    # borrow-in b satisfies the same prefix recurrence with
    # g = (d < 0), pr = (d == 0) on the negated difference domain.
    p = limb_col(_P_LIMBS, a)
    d = e - p
    g = (d < 0).astype(jnp.int32)
    pr = (d == 0).astype(jnp.int32)  # zero diff propagates an incoming borrow
    dd = 1
    gg, pp = g, pr
    while dd < NLIMB:
        gs = jnp.concatenate([_zeros_rows(gg, dd), gg[:-dd]], axis=0)
        ps = jnp.concatenate([_zeros_rows(pp, dd), pp[:-dd]], axis=0)
        gg = gg | (pp & gs)
        pp = pp & ps
        dd *= 2
    bin_ = jnp.concatenate([_zeros_rows(gg, 1), gg[:-1]], axis=0)
    sub = (d - bin_) & MASK
    ge = gg[NLIMB - 1] == 0  # no net borrow -> e >= p
    return jnp.where(ge[None], sub, e)


@named_region("fe_is_zero")
def fe_is_zero(a, bounds: Bounds = None):
    """a ≡ 0 mod p? Returns (...,) bool (batch shape without limb axis)."""
    e = _exact_lt_2p(a, list(W2) if bounds is None else list(bounds))
    p = limb_col(_P_LIMBS, a)
    return jnp.all(e == 0, axis=0) | jnp.all(e == p, axis=0)


def fe_is_zero_many(vals: Sequence):
    """Zero tests for k same-shape elements via one widened dispatch: the
    operands are concatenated along the lane axis so the lookahead runs
    once at k-fold width (cheaper than k narrow chains)."""
    k = len(vals)
    cat = jnp.concatenate(list(vals), axis=-1)
    z = fe_is_zero(cat)
    n = z.shape[-1] // k
    return tuple(z[..., i * n : (i + 1) * n] for i in range(k))


def fe_eq(a, b):
    """a ≡ b mod p? (weak inputs)"""
    return fe_is_zero(fe_sub(a, b))


def fe_pow_const(a, e: int):
    """a^e mod p for a static exponent (square-and-multiply under
    lax.scan; schedule fixed at trace time, graph stays tiny)."""
    from jax import lax

    bits = jnp.asarray([int(c) for c in bin(e)[2:]], dtype=jnp.int32)

    def body(acc, bit):
        acc = fe_sqr(acc)
        return jnp.where(bit == 1, fe_mul(acc, a), acc), None

    acc, _ = lax.scan(body, a, bits[1:])
    return acc


def _sqr_n(x, n: int):
    """n repeated squarings under fori_loop (body compiled once per call
    site — Mosaic-lowerable, unlike a scan with stacked outputs)."""
    from jax import lax

    if n == 0:
        return x
    if n == 1:
        return fe_sqr(x)
    return lax.fori_loop(0, n, lambda i, acc: fe_sqr(acc), x)


def fe_pow_runs(x, e: int):
    """x^e for a static exponent whose binary form has long 1-runs (both
    secp256k1 field exponents do: (p+1)/4 and p-2 are runs of 223 and 22
    ones plus a short tail). Addition-chain over run blocks: the same
    ~log2(e) squarings as the bit ladder but ~18 multiplies instead of
    popcount(e) ~ 223/239 — the multiply count is what the bit ladder
    wastes (`secp256k1/src/field_*_impl.h` uses the same structure; chain
    derived independently). Exponent bookkeeping is asserted at trace
    time, so a wrong chain cannot trace, let alone compile."""
    assert e > 0
    # rep[k] holds (value, exponent) with exponent == 2^k - 1.
    rep = {1: (x, 1)}

    def get_rep(k: int):
        if k not in rep:
            a = k // 2
            b = k - a
            va, ea = get_rep(a)
            vb, eb = get_rep(b)
            val = fe_mul(_sqr_n(va, b), vb)
            ee = (ea << b) + eb
            assert ee == (1 << k) - 1
            rep[k] = (val, ee)
        return rep[k]

    runs = []  # (bit, length), MSB-first
    for ch in bin(e)[2:]:
        bit = int(ch)
        if runs and runs[-1][0] == bit:
            runs[-1][1] += 1
        else:
            runs.append([bit, 1])
    assert runs[0][0] == 1
    acc, e_acc = get_rep(runs[0][1])
    pending = 0
    for bit, length in runs[1:]:
        if bit == 0:
            pending += length
            continue
        blk, eb = get_rep(length)
        acc = fe_mul(_sqr_n(acc, pending + length), blk)
        e_acc = (e_acc << (pending + length)) + eb
        pending = 0
    acc = _sqr_n(acc, pending)
    e_acc <<= pending
    assert e_acc == e, "power chain bookkeeping broke"
    return acc


@named_region("fe_inv")
def fe_inv(a):
    """a^(p-2) mod p (Fermat inverse; 0 -> 0).

    Scan-based ladder: ONE compiled body — the XLA-path form (CPU test
    compiles stay fast). The Pallas kernel uses `fe_inv_chain` instead
    (Mosaic cannot lower the scan, and compiles the chain's fori_loop
    bodies cheaply)."""
    return fe_pow_const(a, P_INT - 2)


def fe_inv_chain(a):
    """Addition-chain Fermat inverse (~18 muls instead of ~239): the
    Pallas-kernel form of fe_inv. Bit-identical results."""
    return fe_pow_runs(a, P_INT - 2)


def fe_batch_inv(a, zero_mask):
    """Per-lane inverse over a (20, B) batch via Montgomery's trick.

    Two associative scans of fe_mul along the batch axis (prefix and
    suffix products) plus ONE tiny Fermat inversion of the grand product:
    ~4 field muls per lane instead of ~500 (`inv_i = pre_{i-1} * suf_{i+1}
    * inv(total)`). This is the batch-axis analogue of the reference's
    batch-inverse pattern — the lanes already advance in lockstep, so the
    scan tree is log-depth whole-array work.

    `zero_mask` (B,) marks lanes whose input is ≡ 0 (they would zero the
    whole product); such lanes contribute 1 to the scans and return 0,
    preserving the fe_inv(0) = 0 convention.
    """
    from jax import lax

    one = jnp.zeros_like(a).at[0].set(1)
    aa = jnp.where(zero_mask[None], one, a)
    pre = lax.associative_scan(fe_mul, aa, axis=1)
    suf = jnp.flip(lax.associative_scan(fe_mul, jnp.flip(aa, 1), axis=1), 1)
    tinv = fe_inv(pre[:, -1:])  # (20, 1): one narrow Fermat chain
    left = jnp.concatenate([one[:, :1], pre[:, :-1]], axis=1)
    right = jnp.concatenate([suf[:, 1:], one[:, :1]], axis=1)
    out = fe_mul(fe_mul(left, right), jnp.broadcast_to(tinv, a.shape))
    return jnp.where(zero_mask[None], jnp.zeros_like(a), out)


def fe_sqrt(a):
    """Candidate square root a^((p+1)/4) (p ≡ 3 mod 4). The caller must
    check candidate^2 == a; for non-residues the candidate is garbage.
    Scan-based (XLA path); the Pallas kernel uses `fe_sqrt_chain`."""
    return fe_pow_const(a, (P_INT + 1) // 4)


def fe_sqrt_chain(a):
    """Addition-chain sqrt candidate (~18 muls instead of ~223): the
    Pallas-kernel form of fe_sqrt. Bit-identical results."""
    return fe_pow_runs(a, (P_INT + 1) // 4)
