"""Scalar-schedule prover: machine-checked certificates for the scalar
pipeline that the kernel arc is about to rewrite.

The interval / Pallas / f32-exactness provers (PR 1/4/16) certify
*limb-level* arithmetic; this module certifies the *scalar-level*
semantics above it — the digit recoders, the GLV lattice split, and the
doubling/add window schedules — so a window-order swap or a carry
off-by-one is a static-analysis FAIL instead of a silent consensus bug.
Four legs, all fail-closed (an unproven or unevaluable claim is FAIL,
never VACUOUS):

1. **Bit-slice recombination theorems** (`_Sym`): each recoder
   (`scalar_bits`-based `_digits`, `_digits128`, the raw digits feeding
   `_signed_digits128`, and `bytes_to_limbs` packing) is abstractly
   interpreted over symbolic bit variables b_i.  Every window digit must
   equal Σ_{i<width} 2^i · b_{w·width+i} *exactly* — which makes the
   radix recombination Σ_w d_w · 2^(w·width) = Σ_i 2^i · b_i an identity,
   not a sampled test.  The interval domain's congruence facts
   (`interval.AbstractArray.cong`, added alongside this module) prove the
   same plane-divisibility/range structure inside the abstract
   interpreter for the windows whose weights fit int32.

2. **Carry-automaton proof** of `_signed_digits128`: the recoder is a
   2×32-state automaton (carry × window value).  We (a) enumerate every
   transition of the spec δ and check the telescoping invariant
   d + 32·c' = v + c with d ∈ [-16, 15], (b) check the traced function
   is literally one length-26 forward scan over the proven-exact raw
   digits, and (c) drive the *device* function through all 1584
   reachable (window, value, carry) configurations in one batched call
   and compare against an independent host recoder — including the
   claimed "top window never carries out (bits 125..127 + carry ≤ 8 <
   16)" fact at ops/pallas_kernel.py:109, which is discharged
   mechanically here instead of trusted.

3. **Exact GLV certificate** for `crypto/glv.py`: λ³ ≡ 1 (mod n),
   β³ ≡ 1 (mod p), λ·G = (β·x, y) on the actual generator, the lattice
   basis relation (adjugate rows A_i = minrep(-λ·B_i mod n) with
   determinant A1·B2 − A2·B1 = n), and the worst-case rounding bound
   |k1|, |k2| ≤ (|A1|+|A2|)//2 + 1 < 2^128 derived from exact integer
   arithmetic — plus a structured-k panel through the real
   `split_lambda`.  Corrupting any constant breaks the determinant or a
   cube identity, so the certificate is not refutable by re-deriving
   from the corrupted values.

4. **Schedule ledger**: the production ladders
   (`double_scalar_mult_glv`, the Pallas `_kernel_body`) are executed
   eagerly under an instrumented `lax.fori_loop` that runs every window
   iteration with a concrete Python index while spies record each
   jacobian double/add and each digit-array read.  From the recording we
   build the weight ledger: accumulating R ← 2^D·R + d_{w(i)}·P over the
   loop gives digit w a final coefficient of 2^(D·(count−1−i)); the
   prover asserts coefficient(w) == 2^(width·w) for EVERY window — which
   is exactly "the ledger sum equals the recoder's radix decomposition"
   and catches swapped window order, dropped doublings, and
   doubling-count drift in one identity.  Table-entry multiples are
   certified separately (host differential for `_p_table` / `_g_table`;
   object-flow chain proof + `iota+1` index check for the Pallas VMEM
   table), and the XLA walks double as end-to-end differentials against
   the exact host implementation (all iterations really run, in order,
   on concrete values).

`NEGATIVES` holds planted-unsound variants (wrong carry fold, swapped
window order, dropped doubling, out-of-range digit weights, corrupted
GLV constant); `analyze_negative` must REJECT each one — the same
discipline as `pallas_check.NEGATIVES` and the f32 exactness toys.

Registering a new recoder or schedule: add the function name to
`REGISTERED_RECODERS` (host_lint's scalar-coverage rule requires it),
add a `_target_*` prover entry to `TARGETS`, and give it a planted
negative if it introduces a new failure mode.
"""

from __future__ import annotations

import ast
import inspect
import math
import textwrap
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import interval
from ..crypto import glv as glv_mod
from ..crypto import secp_host as host
from ..ops import curve as curve_mod
from ..ops import limbs as limbs_mod
from ..ops import pallas_kernel as pk_mod

RADIX = limbs_mod.RADIX
MASK = limbs_mod.MASK
NLIMB = limbs_mod.NLIMB


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------

@dataclass
class CertResult:
    """One certificate: THEOREM (proved, with facts), VACUOUS (ran but
    proved nothing), or FAIL (refuted or unevaluable — fail closed)."""

    name: str
    status: str                      # THEOREM | VACUOUS | FAIL
    facts: Dict[str, Any] = field(default_factory=dict)
    failures: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.status == "THEOREM"

    def to_dict(self) -> dict:
        return {"name": self.name, "status": self.status, "ok": self.ok,
                "facts": self.facts, "failures": self.failures}


def _finish(name: str, facts: Dict[str, Any],
            failures: List[str]) -> CertResult:
    if failures:
        return CertResult(name, "FAIL", facts, failures)
    if not facts:
        return CertResult(name, "VACUOUS", facts, ["no facts proven"])
    return CertResult(name, "THEOREM", facts, [])


# --------------------------------------------------------------------------
# Leg 1 — symbolic bit-slice evaluator
# --------------------------------------------------------------------------

class SymUnsupported(Exception):
    """A primitive or operand shape the bit-slice domain cannot model
    exactly.  Callers turn this into FAIL — never into a skip."""


class Lin:
    """Exact integer-linear form  const + Σ coeff_b · b  over bit
    variables b ∈ {0, 1}.  All arithmetic is exact Python-int; any
    operation that cannot be represented exactly raises SymUnsupported.

    The *packed* normal form (const == 0, every coefficient a distinct
    power of two, at most one term per bit) is what justifies the
    nonlinear ops: `x >> c` drops positions < c exactly (their sum is
    < 2^c), `x & (2^t - 1)` keeps positions < t, and `x | y` with
    disjoint position sets is addition."""

    __slots__ = ("terms", "const")

    def __init__(self, terms: Optional[Dict[int, int]] = None,
                 const: int = 0):
        self.terms = {b: c for b, c in (terms or {}).items() if c != 0}
        self.const = const

    # -- helpers ----------------------------------------------------------
    @property
    def is_const(self) -> bool:
        return not self.terms

    def value_bounds(self) -> Tuple[int, int]:
        lo = self.const + sum(c for c in self.terms.values() if c < 0)
        hi = self.const + sum(c for c in self.terms.values() if c > 0)
        return lo, hi

    def packed(self) -> Optional[Dict[int, int]]:
        """{bit-position: bit-id} if in packed normal form, else None."""
        if self.const != 0:
            return None
        pos: Dict[int, int] = {}
        for b, c in self.terms.items():
            if c <= 0 or (c & (c - 1)) != 0:
                return None
            p = c.bit_length() - 1
            if p in pos:
                return None
            pos[p] = b
        return pos

    def __eq__(self, other):
        if isinstance(other, int):
            return self.is_const and self.const == other
        if not isinstance(other, Lin):
            return NotImplemented
        return self.const == other.const and self.terms == other.terms

    def __hash__(self):
        return hash((self.const, tuple(sorted(self.terms.items()))))

    def __repr__(self):
        ts = " + ".join(f"{c}*b{b}" for b, c in sorted(self.terms.items()))
        return f"Lin({self.const}{' + ' + ts if ts else ''})"

    # -- exact ring ops ---------------------------------------------------
    @staticmethod
    def _coerce(x) -> "Lin":
        if isinstance(x, Lin):
            return x
        if isinstance(x, (int, np.integer)):
            return Lin(const=int(x))
        raise SymUnsupported(f"cannot coerce {type(x).__name__}")

    def __add__(self, other):
        o = Lin._coerce(other)
        t = dict(self.terms)
        for b, c in o.terms.items():
            t[b] = t.get(b, 0) + c
        return Lin(t, self.const + o.const)

    __radd__ = __add__

    def __neg__(self):
        return Lin({b: -c for b, c in self.terms.items()}, -self.const)

    def __sub__(self, other):
        return self + (-Lin._coerce(other))

    def __rsub__(self, other):
        return (-self) + Lin._coerce(other)

    def __mul__(self, other):
        o = Lin._coerce(other)
        if o.is_const:
            k = o.const
            return Lin({b: c * k for b, c in self.terms.items()},
                       self.const * k)
        if self.is_const:
            k = self.const
            return Lin({b: c * k for b, c in o.terms.items()}, o.const * k)
        raise SymUnsupported("nonlinear product of two symbolic forms")

    __rmul__ = __mul__

    def __lshift__(self, other):
        o = Lin._coerce(other)
        if not o.is_const or o.const < 0:
            raise SymUnsupported("symbolic/negative shift amount")
        return self * (1 << o.const)

    def __rshift__(self, other):
        o = Lin._coerce(other)
        if not o.is_const or o.const < 0:
            raise SymUnsupported("symbolic/negative shift amount")
        c = o.const
        if self.is_const:
            if self.const < 0:
                raise SymUnsupported("rshift of negative constant")
            return Lin(const=self.const >> c)
        pos = self.packed()
        if pos is None:
            raise SymUnsupported("rshift of non-packed form")
        return Lin({b: 1 << (p - c) for p, b in pos.items() if p >= c})

    def __and__(self, other):
        o = Lin._coerce(other)
        if self.is_const and o.is_const:
            if self.const < 0 or o.const < 0:
                raise SymUnsupported("bitand of negative constants")
            return Lin(const=self.const & o.const)
        if o.is_const:
            sym, mask = self, o.const
        elif self.is_const:
            sym, mask = o, self.const
        else:
            raise SymUnsupported("bitand of two symbolic forms")
        if mask < 0 or (mask & (mask + 1)) != 0:
            raise SymUnsupported(f"bitand with non-low-mask {mask:#x}")
        t = mask.bit_length()          # mask == 2^t - 1
        pos = sym.packed()
        if pos is None:
            raise SymUnsupported("bitand of non-packed form")
        return Lin({b: 1 << p for p, b in pos.items() if p < t})

    def __or__(self, other):
        o = Lin._coerce(other)
        if self.is_const and self.const == 0:
            return o
        if o.is_const and o.const == 0:
            return self
        if self.is_const and o.is_const:
            if self.const < 0 or o.const < 0:
                raise SymUnsupported("bitor of negative constants")
            return Lin(const=self.const | o.const)
        pa, pb = self.packed(), o.packed()
        if pa is None or pb is None or (set(pa) & set(pb)):
            raise SymUnsupported("bitor of overlapping/non-packed forms")
        return self + o

    __ror__ = __or__


def _sym_const(arr: np.ndarray) -> np.ndarray:
    out = np.empty(arr.shape, dtype=object)
    flat = out.reshape(-1)
    src = np.asarray(arr).reshape(-1)
    for i in range(flat.shape[0]):
        flat[i] = Lin(const=int(src[i]))
    return out


def _sym_eval(closed, args: List[np.ndarray]) -> List[np.ndarray]:
    """Interpret a ClosedJaxpr over numpy object arrays of `Lin`."""
    return _sym_eval_jaxpr(closed.jaxpr, closed.consts, args)


def _sym_eval_jaxpr(jaxpr, consts, args):
    env: Dict[Any, np.ndarray] = {}

    def read(v):
        if isinstance(v, jax.extend.core.Literal):
            return _sym_const(np.asarray(v.val))
        return env[v]

    def write(v, val):
        env[v] = val

    for v, c in zip(jaxpr.constvars, consts):
        write(v, _sym_const(np.asarray(c)))
    for v, a in zip(jaxpr.invars, args):
        write(v, np.asarray(a, dtype=object))

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [read(v) for v in eqn.invars]
        p = eqn.params
        if prim in ("jit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "remat", "checkpoint"):
            inner = p.get("jaxpr") or p.get("call_jaxpr")
            if hasattr(inner, "jaxpr"):        # ClosedJaxpr
                outs = _sym_eval_jaxpr(inner.jaxpr, inner.consts, ins)
            else:
                outs = _sym_eval_jaxpr(inner, (), ins)
        elif prim == "add":
            outs = [np.add(*np.broadcast_arrays(*ins))]
        elif prim == "sub":
            outs = [np.subtract(*np.broadcast_arrays(*ins))]
        elif prim == "mul":
            outs = [np.multiply(*np.broadcast_arrays(*ins))]
        elif prim == "neg":
            outs = [np.negative(ins[0])]
        elif prim == "and":
            outs = [np.bitwise_and(*np.broadcast_arrays(*ins))]
        elif prim == "or":
            outs = [np.bitwise_or(*np.broadcast_arrays(*ins))]
        elif prim in ("shift_right_logical", "shift_right_arithmetic"):
            # identical on our domain: packed forms are non-negative by
            # construction and constant operands are checked >= 0.
            outs = [np.right_shift(*np.broadcast_arrays(*ins))]
        elif prim == "shift_left":
            outs = [np.left_shift(*np.broadcast_arrays(*ins))]
        elif prim == "reduce_sum":
            outs = [np.sum(ins[0], axis=tuple(p["axes"]))]
        elif prim == "convert_element_type":
            nd = p["new_dtype"]
            if not jnp.issubdtype(nd, jnp.integer):
                raise SymUnsupported(f"convert to non-integer {nd}")
            outs = [ins[0]]            # exactness checked by the caller's
                                       # range facts; int->int is identity
                                       # whenever the value fits, and every
                                       # theorem also proves the range.
        elif prim == "reshape":
            outs = [np.reshape(ins[0], p["new_sizes"])]
        elif prim == "squeeze":
            outs = [np.squeeze(ins[0], axis=tuple(p["dimensions"]))]
        elif prim == "expand_dims":
            outs = [np.expand_dims(ins[0], axis=tuple(p["dimensions"]))]
        elif prim == "transpose":
            outs = [np.transpose(ins[0], p["permutation"])]
        elif prim == "rev":
            sl = tuple(slice(None, None, -1) if d in p["dimensions"]
                       else slice(None) for d in range(ins[0].ndim))
            outs = [ins[0][sl]]
        elif prim == "broadcast_in_dim":
            shape = p["shape"]
            newshape = [1] * len(shape)
            for i, d in enumerate(p["broadcast_dimensions"]):
                newshape[d] = ins[0].shape[i]
            outs = [np.broadcast_to(ins[0].reshape(newshape), shape)]
        elif prim == "slice":
            sl = tuple(slice(s, l, st) for s, l, st in
                       zip(p["start_indices"], p["limit_indices"],
                           p["strides"] or [1] * len(p["start_indices"])))
            outs = [ins[0][sl]]
        elif prim == "concatenate":
            outs = [np.concatenate(ins, axis=p["dimension"])]
        elif prim == "iota":
            idx = np.indices(p["shape"])[p["dimension"]]
            outs = [_sym_const(idx)]
        elif prim == "pad":
            x, pv = ins[0], ins[1].reshape(-1)[0]
            cfg = p["padding_config"]
            shape = tuple(lo + hi + max(0, x.shape[i] - 1) * it + x.shape[i]
                          for i, (lo, hi, it) in enumerate(cfg))
            out = np.empty(shape, dtype=object)
            out[...] = pv
            sl = tuple(slice(lo, lo + max(0, x.shape[i] - 1) * (it + 1) + 1,
                             it + 1)
                       for i, (lo, hi, it) in enumerate(cfg))
            out[sl] = x
            outs = [out]
        elif prim == "copy" or prim == "stop_gradient":
            outs = [ins[0]]
        else:
            raise SymUnsupported(f"primitive `{prim}` outside the "
                                 "bit-slice domain")
        for v, o in zip(eqn.outvars, outs):
            write(v, o)
    return [read(v) for v in jaxpr.outvars]


def _seed_limb_bits(nlimb: int) -> np.ndarray:
    """(nlimb, 1) object array: limb l = Σ_{i<RADIX} 2^i · b_{RADIX·l+i}.
    Bit id == absolute bit position of the packed integer."""
    out = np.empty((nlimb, 1), dtype=object)
    for l in range(nlimb):
        out[l, 0] = Lin({RADIX * l + i: 1 << i for i in range(RADIX)})
    return out


def _seed_byte_bits(nbytes: int) -> np.ndarray:
    """(1, nbytes) object array: byte k = Σ_{i<8} 2^i · b_{8k+i}."""
    out = np.empty((1, nbytes), dtype=object)
    for k in range(nbytes):
        out[0, k] = Lin({8 * k + i: 1 << i for i in range(8)})
    return out


def _expected_window(w: int, width: int) -> Lin:
    return Lin({w * width + i: 1 << i for i in range(width)})


def _prove_digit_slices(name: str, fn, seed: np.ndarray,
                        count: int, width: int,
                        facts: Dict[str, Any],
                        failures: List[str]) -> None:
    """Core recombination theorem: fn(seed)[w] == Σ 2^i b_{w·width+i}."""
    try:
        shape = tuple(int(d) for d in seed.shape)
        closed = jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct(shape, jnp.int32))
        (digits,) = _sym_eval(closed, [seed])
    except SymUnsupported as e:
        failures.append(f"{name}: symbolic evaluation failed: {e}")
        return
    except Exception as e:  # noqa: BLE001 — unevaluable is FAIL
        failures.append(f"{name}: {type(e).__name__}: {e}")
        return
    if digits.shape[0] != count:
        failures.append(f"{name}: expected {count} windows, traced "
                        f"{digits.shape[0]}")
        return
    max_digit = 0
    recomb = Lin()
    for w in range(count):
        d = digits[w].reshape(-1)[0]
        want = _expected_window(w, width)
        if d != want:
            failures.append(
                f"{name}: window {w} is {d!r}, not the exact bit slice "
                f"{want!r} — recombination broken")
            continue
        lo, hi = d.value_bounds()
        max_digit = max(max_digit, hi)
        if not (0 <= lo and hi <= (1 << width) - 1):
            failures.append(f"{name}: window {w} range [{lo},{hi}] "
                            f"outside [0, 2^{width}-1]")
        recomb = recomb + d * (1 << (w * width))
    want_total = Lin({i: 1 << i for i in range(count * width)})
    if recomb != want_total:
        failures.append(f"{name}: Σ d_w·2^(w·width) != Σ 2^i·b_i over the "
                        f"consumed {count * width} bits")
    if not failures:
        facts[name] = {
            "windows": count, "width": width,
            "bits_consumed": count * width,
            "max_digit": max_digit,
            "recombination": "sum(d_w * 2^(w*width)) == sum(2^i * b_i)",
        }


def _prove_bytes_to_limbs(nbytes: int, nlimb: int,
                          facts: Dict[str, Any],
                          failures: List[str]) -> None:
    name = f"bytes_to_limbs[{nbytes}B->{nlimb}L]"
    try:
        closed = jax.make_jaxpr(
            lambda u8: limbs_mod.bytes_to_limbs(u8, nlimb=nlimb))(
                jax.ShapeDtypeStruct((1, nbytes), jnp.uint8))
        (limbs,) = _sym_eval(closed, [_seed_byte_bits(nbytes)])
    except SymUnsupported as e:
        failures.append(f"{name}: symbolic evaluation failed: {e}")
        return
    except Exception as e:  # noqa: BLE001
        failures.append(f"{name}: {type(e).__name__}: {e}")
        return
    nbits = nbytes * 8
    recomb = Lin()
    for l in range(limbs.shape[0]):
        got = limbs[l].reshape(-1)[0]
        want = Lin({RADIX * l + i: 1 << i for i in range(RADIX)
                    if RADIX * l + i < nbits})
        if got != want:
            failures.append(f"{name}: limb {l} is {got!r}, expected the "
                            f"exact bit slice {want!r}")
            continue
        recomb = recomb + got * (1 << (RADIX * l))
    if recomb != Lin({i: 1 << i for i in range(nbits)}):
        failures.append(f"{name}: Σ limb_l·2^(13·l) != Σ 2^i·b_i")
    if not failures:
        facts[name] = {"bytes": nbytes, "limbs": nlimb,
                       "recombination":
                       "sum(limb_l * 2^(13*l)) == sum(2^i * b_i)"}


def _prove_cong_planes(facts: Dict[str, Any],
                       failures: List[str]) -> None:
    """Interval+congruence leg: run the weighted-plane recombiner through
    the abstract interpreter.  plane_w = d_w · 2^(4w) must carry the
    congruence fact ≡ 0 (mod 2^(4w)) and the interval [0, 2^(4w+4)-2^(4w)]
    — divisibility + range + disjoint support is the analyzer-level shape
    of the exact recombination (the full identity is leg 1's _Sym proof;
    int32 caps the planes at window 6)."""
    n_planes = 7                      # 4·6+4 = 28 bits < int32

    def planes(limbs):
        d = curve_mod._digits(limbs, 4, 64)
        return jnp.stack([d[w] << (4 * w) for w in range(n_planes)], axis=0)

    try:
        rep = interval.analyze(planes, [jnp.zeros((NLIMB, 2), jnp.int32)],
                               in_bounds={0: (0, MASK)},
                               name="scalar.digit_planes")
    except Exception as e:  # noqa: BLE001
        failures.append(f"cong-planes: {type(e).__name__}: {e}")
        return
    if not rep.ok:
        failures.append("cong-planes: interval prover found violations: "
                        + "; ".join(str(v) for v in rep.violations[:3]))
        return
    if not rep.out_cong or not rep.out_cong[0]:
        failures.append("cong-planes: analyzer derived no congruence "
                        "facts for the digit planes")
        return
    rows = rep.out_cong[0]
    if len(rows) == 1:
        rows = rows * n_planes
    proved = 0
    for w in range(n_planes):
        fact = rows[w]
        m = 1 << (4 * w)
        if w == 0:
            proved += 1               # ≡ 0 (mod 1) is trivially carried
            continue
        if fact is None or fact[0] % m != 0 and fact[0] != 0 or \
                fact[1] % m != 0:
            failures.append(
                f"cong-planes: plane {w} fact {fact} does not prove "
                f"≡ 0 (mod 2^{4 * w})")
            continue
        proved += 1
    lo_hi = rep.out_bounds[0] if rep.out_bounds else []
    if not failures:
        facts["cong_planes"] = {
            "planes": proved,
            "rule": "plane_w ≡ 0 (mod 2^(4w)), plane_w < 2^(4w+4)",
            "bounds_rows": len(lo_hi),
        }


def _target_digits() -> CertResult:
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    _prove_digit_slices("_digits[w4,c64]",
                        lambda l: curve_mod._digits(l, 4, 64),
                        _seed_limb_bits(NLIMB), 64, 4, facts, failures)
    _prove_digit_slices("_digits[w8,c32]",
                        lambda l: curve_mod._digits(l, 8, 32),
                        _seed_limb_bits(NLIMB), 32, 8, facts, failures)
    _prove_cong_planes(facts, failures)
    return _finish("scalar._digits", facts, failures)


def _target_digits128() -> CertResult:
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    _prove_digit_slices("_digits128[w4,c32]",
                        lambda l: curve_mod._digits128(l, 32, 4),
                        _seed_limb_bits(10), 32, 4, facts, failures)
    _prove_digit_slices("_digits128[w5,c26]",
                        lambda l: curve_mod._digits128(l, 26, 5),
                        _seed_limb_bits(10), 26, 5, facts, failures)
    # Composed with the device unpack of a 16-byte (< 2^128) value, the
    # top 5-bit window must touch only bits 125..127 — the premise of the
    # no-carry-out claim the automaton leg discharges.
    try:
        closed = jax.make_jaxpr(
            lambda u8: curve_mod._digits128(
                limbs_mod.bytes_to_limbs(u8, nlimb=10), 26, 5))(
                    jax.ShapeDtypeStruct((1, 16), jnp.uint8))
        (raw,) = _sym_eval(closed, [_seed_byte_bits(16)])
        top = raw[25].reshape(-1)[0]
        want = Lin({125: 1, 126: 2, 127: 4})
        if top != want:
            failures.append(f"top window of _digits128(bytes16) is "
                            f"{top!r}, expected bits 125..127 only")
        else:
            facts["top_window"] = {"bits": [125, 126, 127], "max": 7}
    except SymUnsupported as e:
        failures.append(f"top-window slice: {e}")
    except Exception as e:  # noqa: BLE001
        failures.append(f"top-window slice: {type(e).__name__}: {e}")
    return _finish("scalar._digits128", facts, failures)


def _target_bytes_to_limbs() -> CertResult:
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    _prove_bytes_to_limbs(32, NLIMB, facts, failures)
    _prove_bytes_to_limbs(16, 10, facts, failures)
    return _finish("scalar.bytes_to_limbs", facts, failures)


def _target_bytes_from_words() -> CertResult:
    """Digest unpack `sha256._bytes_from_words`: byte j of the output is
    the exact big-endian 8-bit slice of word j//4 — same bit-slice domain
    as the limb packers (the host_lint scalar-coverage rule flags this
    function's `(w >> shifts) & 0xFF` extraction, so it is certified)."""
    from ..ops import sha256 as sha_mod

    facts: Dict[str, Any] = {}
    failures: List[str] = []
    name = "bytes_from_words[8W->32B]"
    seed = np.empty((8,), dtype=object)
    for w in range(8):
        seed[w] = Lin({32 * w + i: 1 << i for i in range(32)})
    try:
        closed = jax.make_jaxpr(sha_mod._bytes_from_words)(
            jax.ShapeDtypeStruct((8,), jnp.uint32))
        (out,) = _sym_eval(closed, [seed])
    except SymUnsupported as e:
        failures.append(f"{name}: symbolic evaluation failed: {e}")
        return _finish("sha256.bytes_from_words", facts, failures)
    except Exception as e:  # noqa: BLE001 — unevaluable is FAIL
        failures.append(f"{name}: {type(e).__name__}: {e}")
        return _finish("sha256.bytes_from_words", facts, failures)
    for j in range(32):
        word, pos = j // 4, j % 4
        sh = 8 * (3 - pos)  # big-endian byte order within each word
        want = Lin({32 * word + sh + i: 1 << i for i in range(8)})
        got = out.reshape(-1)[j]
        if got != want:
            failures.append(f"{name}: byte {j} is {got!r}, expected the "
                            f"big-endian slice {want!r}")
    if not failures:
        facts[name] = {"words": 8, "bytes": 32, "order": "big-endian",
                       "rule": "byte j == bits 8*(3-j%4)..+8 of word j//4"}
    return _finish("sha256.bytes_from_words", facts, failures)


# --------------------------------------------------------------------------
# Leg 2 — carry automaton for _signed_digits128
# --------------------------------------------------------------------------

def _ref_signed_recode(x: int, *, threshold: int = 16,
                       wrap: int = 32) -> List[int]:
    """Independent host recoder: 26 signed 5-bit windows, LSB first."""
    assert 0 <= x < 1 << 128
    digits = []
    carry = 0
    for w in range(pk_mod.SGLV_WINDOWS):
        t = ((x >> (5 * w)) & 31) + carry
        carry = 1 if t >= threshold else 0
        digits.append(t - wrap * carry)
    assert carry == 0, "top window carried out"
    return digits


def _count_scans(jaxpr, found: List[Any]) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _count_scans(inner, found)


def prove_carry_automaton(step_fn=None) -> CertResult:
    """Exhaustive proof of the signed-digit recoder.

    `step_fn(t) -> (carry', digit)` defaults to the production fold
    (t >= 16 → t − 32); negatives pass a corrupted fold."""
    facts: Dict[str, Any] = {}
    failures: List[str] = []

    def default_step(t: int) -> Tuple[int, int]:
        c = 1 if t >= 16 else 0
        return c, t - 32 * c

    step = step_fn or default_step

    # (a) every transition of the 2 x 32 automaton
    for c in (0, 1):
        for v in range(32):
            cp, d = step(v + c)
            if d + 32 * cp != v + c:
                failures.append(
                    f"automaton: δ({c},{v}) = (c'={cp}, d={d}) breaks the "
                    f"telescoping invariant d + 32·c' = v + c")
            if not (-16 <= d <= 15) or cp not in (0, 1):
                failures.append(
                    f"automaton: δ({c},{v}) digit {d} / carry {cp} "
                    "outside [-16,15] x {0,1}")
    # top window: raw digit 25 ∈ [0,7] (proven by leg 1), so t = v+c <= 8
    for c in (0, 1):
        for v in range(8):
            cp, _ = step(v + c)
            if cp != 0:
                failures.append(
                    f"automaton: top window carries out at (c={c}, v={v}) "
                    "— ops/pallas_kernel.py:109 claim refuted")
    if not failures:
        facts["transitions"] = {"states": 2 * 32, "invariant":
                                "d + 32·c' = v + c, d ∈ [-16,15]",
                                "top_window_no_carry": "t = v+c <= 8 < 16"}

    # (b) the traced recoder is one forward length-26 scan
    try:
        closed = jax.make_jaxpr(pk_mod._signed_digits128)(
            jax.ShapeDtypeStruct((10, 1), jnp.int32))
        scans: List[Any] = []
        _count_scans(closed.jaxpr, scans)
        if len(scans) != 1:
            failures.append(f"structure: expected exactly 1 scan in "
                            f"_signed_digits128, found {len(scans)}")
        else:
            p = scans[0].params
            if p.get("length") != pk_mod.SGLV_WINDOWS:
                failures.append(f"structure: scan length {p.get('length')}"
                                f" != {pk_mod.SGLV_WINDOWS}")
            if p.get("num_carry") != 1:
                failures.append("structure: carry arity != 1")
            if p.get("reverse"):
                failures.append("structure: scan is reversed — carries "
                                "must propagate LSB-first")
            if not failures:
                facts["structure"] = {"scans": 1, "length": 26,
                                      "num_carry": 1, "reverse": False}
    except Exception as e:  # noqa: BLE001
        failures.append(f"structure: {type(e).__name__}: {e}")

    # (c) all 1584 reachable (window, value, carry-in) configurations in
    # one batched device call vs the independent host recoder.  The lane
    # x = v·32^w (+ 16·32^(w-1) to force carry-in 1) reaches window w
    # with value v and carry c: windows < w-1 hold 0, window w-1 holds 16
    # → digit -16, carry 1.
    lanes: List[Tuple[int, int, int, int]] = []   # (x, w, v, c)
    for w in range(pk_mod.SGLV_WINDOWS):
        vmax = 8 if w == pk_mod.SGLV_WINDOWS - 1 else 32
        for v in range(vmax):
            for c in (0, 1):
                if c == 1 and w == 0:
                    continue
                x = v * 32 ** w + (16 * 32 ** (w - 1) if c else 0)
                if x >= 1 << 128:
                    continue
                lanes.append((x, w, v, c))
    xs = [x for x, _, _, _ in lanes]
    arr = np.zeros((10, len(xs)), dtype=np.int32)
    for j, x in enumerate(xs):
        for l in range(10):
            arr[l, j] = (x >> (RADIX * l)) & MASK
    try:
        dev_abs, dev_sgn = jax.jit(pk_mod._signed_digits128)(
            jnp.asarray(arr))
        dev_abs = np.asarray(dev_abs)
        dev_sgn = np.asarray(dev_sgn)
    except Exception as e:  # noqa: BLE001
        failures.append(f"device: {type(e).__name__}: {e}")
        return _finish("scalar._signed_digits128", facts, failures)
    bad = 0
    for j, (x, w, v, c) in enumerate(lanes):
        ref = _ref_signed_recode(x)
        got = [int(dev_abs[i, j]) * (-1 if dev_sgn[i, j] else 1)
               for i in range(pk_mod.SGLV_WINDOWS)]
        if got != ref:
            bad += 1
            if bad <= 3:
                failures.append(
                    f"device: x=2^?·… (w={w}, v={v}, c={c}) recodes to "
                    f"{got[:4]}…, host reference {ref[:4]}…")
        recon = sum(d * 32 ** i for i, d in enumerate(got))
        if recon != x:
            bad += 1
            if bad <= 6:
                failures.append(
                    f"device: Σ d_i·32^i = {recon} != x = {x} "
                    f"(w={w}, v={v}, c={c})")
        if any(abs(d) > 16 for d in got):
            bad += 1
            if bad <= 9:
                failures.append(f"device: digit outside [-16,16] at "
                                f"(w={w}, v={v}, c={c})")
    if bad > 9:
        failures.append(f"device: …{bad - 9} more mismatching lanes")
    if not any(f.startswith("device") for f in failures):
        facts["device_enumeration"] = {
            "lanes": len(lanes),
            "checked": "device == host reference, Σ d·32^w == x, "
                       "|d| <= 16, all (window, value, carry) states",
        }
    return _finish("scalar._signed_digits128", facts, failures)


def _target_signed_digits128() -> CertResult:
    return prove_carry_automaton()


# --------------------------------------------------------------------------
# Leg 3 — exact GLV certificate
# --------------------------------------------------------------------------

def _minrep(x: int, n: int) -> int:
    """Minimal signed representative of x mod n (in (-n/2, n/2])."""
    x %= n
    return x - n if x > n // 2 else x


def prove_glv_constants(B1: Optional[int] = None,
                        B2: Optional[int] = None) -> CertResult:
    """Exact host-side certificate for crypto/glv.py's lattice split.

    With E1 = n·c1 − B2·k and E2 = n·c2 + B1·k (the exact rounding
    errors, |E_i| ≤ n/2 by the round-half-up in split_lambda), the split
    satisfies  n·k2 = −(B1·E1 + B2·E2)  and  n·k1' = −(A1·E1 + A2·E2)
    where A_i = minrep(−λ·B_i mod n) and k1' is the minimal
    representative of k − λ·k2.  Hence |k2| ≤ (|B1|+|B2|)/2 + 1 and
    |k1| ≤ (|A1|+|A2|)/2 + 1, both < 2^128 — derived, not asserted.
    The determinant A1·B2 − A2·B1 = n pins the basis to the curve order:
    corrupting any of B1/B2/λ/n breaks it (or a cube identity)."""
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    n = host.N
    p = host.P
    lam = curve_mod.LAMBDA
    beta = curve_mod.BETA
    b1 = glv_mod._B1 if B1 is None else B1
    b2 = glv_mod._B2 if B2 is None else B2

    if pow(lam, 3, n) != 1 or lam in (1, n - 1):
        failures.append("λ is not a primitive cube root of 1 mod n")
    if pow(beta, 3, p) != 1 or beta in (1, p - 1):
        failures.append("β is not a primitive cube root of 1 mod p")
    if (lam * lam + lam + 1) % n != 0:
        failures.append("λ² + λ + 1 != 0 mod n")
    ep = host.G.mul(lam).to_affine()
    if ep != (beta * host.G_X % p, host.G_Y):
        failures.append("endomorphism λ·G != (β·x_G, y_G) on the "
                        "generator — β and λ are not paired")
    if not failures:
        facts["identities"] = {"lambda_cubed": 1, "beta_cubed": 1,
                               "endomorphism": "λ·G == (β·x_G, y_G)"}

    # basis relation: both rows must be short vectors of the lattice
    # {(a, b) : a + b·λ ≡ 0 mod n}, and the adjugate rows A_i close it
    # with determinant exactly n.
    if (b2 + b1 * lam) % n != 0:      # row (b2, b1): b2 ≡ -b1·λ
        failures.append("basis row (B2, B1) not in the GLV lattice: "
                        "B2 + B1·λ != 0 mod n")
    a1 = _minrep(-lam * b1, n)
    a2 = _minrep(-lam * b2, n)
    det = a1 * b2 - a2 * b1
    if det != n:
        failures.append(f"adjugate determinant A1·B2 − A2·B1 = {det} "
                        f"!= n — lattice constants corrupted")
    bound_k2 = (abs(b1) + abs(b2)) // 2 + 1
    bound_k1 = (abs(a1) + abs(a2)) // 2 + 1
    if bound_k2 >= 1 << 128:
        failures.append(f"|k2| worst case {bound_k2} >= 2^128")
    if bound_k1 >= 1 << 128:
        failures.append(f"|k1| worst case {bound_k1} >= 2^128")
    if not failures:
        facts["lattice"] = {
            "det": "A1·B2 − A2·B1 == n",
            "k1_bound_bits": bound_k1.bit_length(),
            "k2_bound_bits": bound_k2.bit_length(),
        }

    # structured-k panel through the real split, against the exact theory
    panel = [0, 1, 2, n - 1, lam, (n - lam) % n, (1 << 128) - 1, 1 << 128,
             n // 2, n // 2 + 1, lam - 1, lam + 1]
    for k in panel:
        try:
            s_a1, neg1, s_a2, neg2 = glv_mod.split_lambda(k)
        except Exception as e:  # noqa: BLE001
            failures.append(f"split_lambda({k}) raised "
                            f"{type(e).__name__}: {e}")
            continue
        k1 = -s_a1 if neg1 else s_a1
        k2 = -s_a2 if neg2 else s_a2
        if (k1 + lam * k2 - k) % n != 0:
            failures.append(f"split_lambda({k}): k1 + λ·k2 != k mod n")
        if s_a1 >= 1 << 128 or s_a2 >= 1 << 128:
            failures.append(f"split_lambda({k}): half >= 2^128")
        # exact formula re-derivation (independent of glv.py's code path)
        kk = k % n
        c1 = (b2 * kk + n // 2) // n
        c2 = (-b1 * kk + n // 2) // n
        e1 = n * c1 - b2 * kk
        e2 = n * c2 + b1 * kk
        if abs(e1) > n // 2 + 1 or abs(e2) > n // 2 + 1:
            failures.append(f"split_lambda({k}): rounding error exceeds "
                            "n/2 — round-half-up broken")
        want_k2 = -(c1 * glv_mod._B1 + c2 * glv_mod._B2) if B1 is None \
            else -(c1 * b1 + c2 * b2)
        if (k2 - want_k2) % n != 0:
            failures.append(f"split_lambda({k}): k2 disagrees with the "
                            "exact lattice formula")
    if not any("split_lambda" in f for f in failures):
        facts["panel"] = {"cases": len(panel),
                          "checked": "k1 + λ·k2 ≡ k (mod n), halves "
                                     "< 2^128, exact formula match"}
    return _finish("glv.split_lambda", facts, failures)


def _target_glv() -> CertResult:
    return prove_glv_constants()


# --------------------------------------------------------------------------
# Leg 4 — schedule ledger (instrumented eager walk)
# --------------------------------------------------------------------------

_FULL_RUN_CAP = 64   # fori loops at most this long run EVERY iteration
                     # (all window loops qualify: 64/32/26); longer loops
                     # (field-element chains) are sampled and carry no
                     # jacobian events, so the ledger never reads them.


class _Recorder:
    def __init__(self):
        self.loops: List[dict] = []
        self.preamble: List[tuple] = []   # events outside any loop
        self.cur: Optional[dict] = None   # current iteration record
        self.depth = 0

    def event(self, name: str, meta=None):
        if self.depth > 0:
            return
        rec = (name, meta)
        (self.cur["events"] if self.cur is not None
         else self.preamble).append(rec)

    def read(self, array_name: str, index: int):
        if self.depth > 0:
            return
        if self.cur is not None:
            self.cur["reads"].append((array_name, index))
        else:
            self.preamble.append((f"read:{array_name}", index))

    def write(self, array_name: str, index, value_id: int):
        if self.depth > 0:
            return
        rec = (f"write:{array_name}", (index, value_id))
        (self.cur["events"] if self.cur is not None
         else self.preamble).append(rec)


def _spy(rec: _Recorder, name: str, fn):
    def wrapper(*a, **k):
        target = slot = None
        if rec.depth == 0:      # nested jacobian calls are not re-counted
            target = (rec.cur["events"] if rec.cur is not None
                      else rec.preamble)
            target.append((name, {"in": tuple(id(x) for x in a)}))
            slot = len(target) - 1
        rec.depth += 1
        try:
            out = fn(*a, **k)
        finally:
            rec.depth -= 1
        if target is not None:
            outs = out if isinstance(out, tuple) else (out,)
            target[slot] = (name, {"in": target[slot][1]["in"],
                                   "out": tuple(id(x) for x in outs)})
        return out
    return wrapper


def _fake_fori(rec: _Recorder):
    def fori(lo, hi, body, init, **_kw):
        lo, hi = int(lo), int(hi)
        entry = {"lo": lo, "hi": hi, "iters": {}}
        rec.loops.append(entry)
        if hi - lo <= _FULL_RUN_CAP:
            samples = list(range(lo, hi))
        else:
            samples = sorted({lo, lo + 1, hi - 1})
        val = init
        for i in samples:
            it = {"events": [], "reads": []}
            entry["iters"][i] = it
            prev, rec.cur = rec.cur, it
            try:
                val = body(i, val)
            finally:
                rec.cur = prev
        entry["complete"] = (samples == list(range(lo, hi)))
        return val
    return fori


class _SpyArray:
    """Wraps a digit array; records integer row reads."""

    def __init__(self, arr, name: str, rec: _Recorder):
        self._a = arr
        self._name = name
        self._rec = rec

    @property
    def shape(self):
        return self._a.shape

    @property
    def ndim(self):
        return self._a.ndim

    @property
    def dtype(self):
        return self._a.dtype

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            self._rec.read(self._name, int(idx))
        return self._a[idx]


class _FakeRef:
    """pallas Ref stand-in over a jnp array: `[...]` reads/writes with
    integer-index recording."""

    def __init__(self, arr, name: str, rec: _Recorder):
        self._a = jnp.asarray(arr)
        self._name = name
        self._rec = rec

    @property
    def shape(self):
        return self._a.shape

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            self._rec.read(self._name, int(idx))
        return self._a[idx]

    def __setitem__(self, idx, val):
        key = int(idx) if isinstance(idx, (int, np.integer)) else idx
        self._rec.write(self._name,
                        key if isinstance(key, int) else "slice", id(val))
        self._a = self._a.at[idx].set(val)


class _Patched:
    """Context manager: swap module attributes, restore on exit."""

    def __init__(self, mapping: Dict[Tuple[Any, str], Any]):
        self.mapping = mapping
        self.saved: Dict[Tuple[Any, str], Any] = {}

    def __enter__(self):
        for (mod, attr), val in self.mapping.items():
            self.saved[(mod, attr)] = getattr(mod, attr)
            setattr(mod, attr, val)
        return self

    def __exit__(self, *exc):
        for (mod, attr), val in self.saved.items():
            setattr(mod, attr, val)
        return False


_FAST_CACHE: Dict[Any, Any] = {}


def _fast(fn):
    """Jit wrapper preserving the `inf1` static-sentinel contract (None /
    False select different formula variants at trace time; an array is a
    runtime mask).  One compile per (variant, shapes), cached across
    certify calls; the eager ledger walk then costs one dispatch per
    jacobian op instead of hundreds."""
    if fn in _FAST_CACHE:
        return _FAST_CACHE[fn]
    jit_plain = jax.jit(lambda *a: fn(*a))
    jit_inf_false = jax.jit(lambda *a: fn(*a, inf1=False))
    jit_inf_arr = jax.jit(lambda *a: fn(*a[:-1], inf1=a[-1]))

    def call(*a, **k):
        if not k:
            return jit_plain(*a)
        if set(k) != {"inf1"}:
            return fn(*a, **k)
        v = k["inf1"]
        if v is None:
            return jit_plain(*a)
        if v is False:
            return jit_inf_false(*a)
        return jit_inf_arr(*a, v)

    _FAST_CACHE[fn] = call
    return call


def _jacobian_spies(rec: _Recorder, mod) -> Dict[Tuple[Any, str], Any]:
    out: Dict[Tuple[Any, str], Any] = {}
    for name in ("jacobian_double", "jacobian_add_complete",
                 "jacobian_madd_complete", "jacobian_madd_flagged",
                 "jacobian_madd_flagged_ratio", "jacobian_add_flagged",
                 "fe_mul", "fe_sub"):
        if hasattr(mod, name):
            out[(mod, name)] = _spy(rec, name, _fast(getattr(mod, name)))
    return out


_JAC_EVENTS = {"jacobian_double", "jacobian_add_complete",
               "jacobian_madd_complete", "jacobian_madd_flagged",
               "jacobian_madd_flagged_ratio", "jacobian_add_flagged"}


def _window_loops(rec: _Recorder) -> List[dict]:
    """Loops whose iterations contain jacobian-level events (fe chains
    and other helper loops carry none)."""
    out = []
    for loop in rec.loops:
        if any(e[0] in _JAC_EVENTS for it in loop["iters"].values()
               for e in it["events"]):
            out.append(loop)
    return out


def _check_ladder_loop(loop: dict, *, count: int, width: int,
                       digit_arrays: List[str],
                       expect_events: List[str],
                       label: str,
                       failures: List[str]) -> Dict[str, Any]:
    """The core ledger identity for one window loop.

    Every iteration i must perform exactly `width` doublings before its
    adds (expect_events pins the full per-iteration schedule), and read
    window w(i) of each digit array.  Accumulating R ← 2^D·R + d_{w(i)}·P
    gives digit w(i) the final coefficient 2^(D·(count−1−i)); we require
    coefficient(w) == 2^(width·w) for every w — the ledger sum equals
    the radix decomposition Σ d_w·2^(width·w) proven by leg 1."""
    if (loop["lo"], loop["hi"]) != (0, count):
        failures.append(f"{label}: window loop bounds "
                        f"({loop['lo']}, {loop['hi']}) != (0, {count})")
        return {}
    if not loop.get("complete"):
        failures.append(f"{label}: window loop iterations were sampled, "
                        "not exhaustively executed")
        return {}
    doubles_seen = set()
    coeff: Dict[str, Dict[int, int]] = {a: {} for a in digit_arrays}
    for i in range(count):
        it = loop["iters"][i]
        names = [e[0] for e in it["events"]]
        if names != expect_events:
            failures.append(f"{label}: iteration {i} schedule {names} != "
                            f"expected {expect_events}")
            return {}
        doubles_seen.add(sum(1 for nm in names
                             if nm == "jacobian_double"))
        reads = {}
        for arr, idx in it["reads"]:
            if arr in coeff:
                reads.setdefault(arr, []).append(idx)
        for arr in digit_arrays:
            got = reads.get(arr, [])
            if len(got) != 1:
                failures.append(f"{label}: iteration {i} read {arr} "
                                f"{len(got)} times (want once)")
                return {}
            w = got[0]
            if w in coeff[arr]:
                failures.append(f"{label}: window {w} of {arr} read by "
                                "two iterations")
                return {}
            coeff[arr][w] = 1 << (width * (count - 1 - i))
    if doubles_seen != {width}:
        failures.append(f"{label}: doublings per window {doubles_seen} "
                        f"!= recoder width {width} — ledger weight "
                        "mismatch")
        return {}
    for arr in digit_arrays:
        for w in range(count):
            want = 1 << (width * w)
            got = coeff[arr].get(w)
            if got != want:
                failures.append(
                    f"{label}: ledger coefficient of {arr}[{w}] is "
                    f"{'absent' if got is None else hex(got)}, radix "
                    f"decomposition requires 2^{width * w} — window "
                    "order/doubling schedule broken")
                return {}
    return {"windows": count, "doubles_per_window": width,
            "order": "descending (w = count-1-i)",
            "ledger": "coeff(w) == 2^(width*w) for every window"}


def _affine_of(X, Y, Z) -> Optional[Tuple[int, int]]:
    z = limbs_mod.limbs_to_int(np.asarray(Z)[:, 0])
    if z % host.P == 0:
        return None
    x = limbs_mod.limbs_to_int(np.asarray(X)[:, 0])
    y = limbs_mod.limbs_to_int(np.asarray(Y)[:, 0])
    zi = pow(z, host.P - 2, host.P)
    return (x * zi * zi % host.P, y * zi * zi * zi % host.P)


def _limb_col(x: int, n: int = NLIMB) -> jnp.ndarray:
    return jnp.asarray(limbs_mod.int_to_limbs(x, n), jnp.int32)[:, None]


def certify_p_table() -> Tuple[Dict[str, Any], List[str]]:
    """Concrete differential: _p_table rows really hold k·P, k = 0..15."""
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    px, py = _limb_col(host.G_X), _limb_col(host.G_Y)
    TX, TY, TZ = curve_mod._p_table(px, py)
    for k in range(16):
        got = _affine_of(TX[k], TY[k], TZ[k])
        want = host.G.mul(k).to_affine()
        if got != want:
            failures.append(f"_p_table row {k} != {k}·P")
    if not failures:
        facts["p_table"] = {"rows": 16, "rule": "T[k] == k·P"}
    return facts, failures


_GTABLE_CERT: Optional[List[str]] = None


def certify_g_table() -> Tuple[Dict[str, Any], List[str]]:
    """Host certificate: _g_table row (w, j) is affine((j+1)·256^w·G),
    verified incrementally with exact Jacobian point arithmetic (no
    inversions: compare x·Z² ≡ X, y·Z³ ≡ Y mod p)."""
    global _GTABLE_CERT
    if _GTABLE_CERT is not None:
        failures = list(_GTABLE_CERT)
        return ({} if failures else
                {"g_table": {"rows": 32 * 255,
                             "rule": "row (w,j) == (j+1)·256^w·G"}},
                failures)
    failures = []
    gx, gy = curve_mod._g_table()
    gx = np.asarray(gx)
    gy = np.asarray(gy)
    base = host.G                      # 256^w · G, advanced per window
    for w in range(curve_mod.G_WINDOWS):
        ba = base.to_affine()
        acc = host.PointJ.from_affine(*ba)     # (j+1)·base
        for j in range(255):
            a = acc.to_affine() if j else ba
            tx = limbs_mod.limbs_to_int(gx[w, j])
            ty = limbs_mod.limbs_to_int(gy[w, j])
            if (tx, ty) != a:
                failures.append(f"_g_table row ({w}, {j}) != "
                                f"({j + 1})·256^{w}·G")
                if len(failures) > 4:
                    _GTABLE_CERT = failures
                    return {}, failures
            acc = acc.add_affine(*ba)
        for _ in range(8):
            base = base.double()
    _GTABLE_CERT = failures
    if failures:
        return {}, failures
    return {"g_table": {"rows": 32 * 255,
                        "rule": "row (w,j) == (j+1)·256^w·G"}}, []


def _check_g_loop(gl: Dict[str, Any], digits: str, facts: Dict[str, Any],
                  failures: List[str]) -> None:
    """`_fixed_base_mult`'s loop: 32 madds, no doublings — the weights live
    in the table rows (j+1)·256^w·G, so window i reads digit i of `digits`,
    ascending."""
    if (gl["lo"], gl["hi"]) != (0, 32) or not gl.get("complete"):
        failures.append("G loop bounds/completeness wrong")
        return
    for i in range(32):
        it = gl["iters"][i]
        if [e[0] for e in it["events"]] != ["jacobian_madd_complete"]:
            failures.append(f"G loop iteration {i}: schedule "
                            f"{[e[0] for e in it['events']]}")
            return
        reads = [idx for arr, idx in it["reads"] if arr == digits]
        if reads != [i]:
            failures.append(f"G loop iteration {i} reads digit "
                            f"window(s) {reads}, expected [{i}] "
                            "(ascending: weights are in the table)")
            return
    facts["g_loop"] = {"windows": 32, "doubles_per_window": 0,
                       "order": "ascending, table row (j+1)·256^w·G"}


def _target_double_scalar_mult_glv(quick: bool = False) -> CertResult:
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    rec = _Recorder()

    a_int = 0xFACE0FF1CE0DDBA11
    k_int = 0xD1CE0C0DE0BEEF0CAFE0F00D0BADD00D0FACADE0ACC01ADE0DECAF0FAD
    a1, neg1, a2, neg2 = glv_mod.split_lambda(k_int)
    a = _limb_col(a_int)
    db1 = _SpyArray(curve_mod._digits128(_limb_col(a1, 10), 32, 4),
                    "db1", rec)
    db2 = _SpyArray(curve_mod._digits128(_limb_col(a2, 10), 32, 4),
                    "db2", rec)
    n1 = jnp.asarray([neg1])
    n2 = jnp.asarray([neg2])
    px, py = _limb_col(host.G_X), _limb_col(host.G_Y)

    digit_calls: List[Tuple[int, int, str]] = []
    orig_digits = curve_mod._digits

    def digits_spy(limbs, width, count):
        name = f"digits{len(digit_calls)}"
        digit_calls.append((width, count, name))
        return _SpyArray(orig_digits(limbs, width, count), name, rec)

    patches = _jacobian_spies(rec, curve_mod)
    patches[(curve_mod, "_digits")] = digits_spy
    patches[(jax.lax, "fori_loop")] = _fake_fori(rec)
    patches[(lax, "fori_loop")] = patches[(jax.lax, "fori_loop")]
    try:
        with _Patched(patches):
            X, Y, Z, out_inf = curve_mod.double_scalar_mult_glv(
                a, db1, db2, n1, n2, px, py)
    except Exception as e:  # noqa: BLE001
        failures.append(f"ledger walk: {type(e).__name__}: {e}")
        return _finish("curve.double_scalar_mult_glv", facts, failures)

    if [(w, c) for w, c, _ in digit_calls] != [(8, 32)]:
        failures.append(f"recoder calls {digit_calls} != expected "
                        "[(8,32) G digits]")
        return _finish("curve.double_scalar_mult_glv", facts, failures)
    wloops = _window_loops(rec)
    if len(wloops) != 2:
        failures.append(f"found {len(wloops)} jacobian window loops, "
                        "expected 2 (GLV ladder + G madd loop)")
        return _finish("curve.double_scalar_mult_glv", facts, failures)
    # per-iteration schedule pins β onto the SECOND (λ-half) add: the
    # lone top-level fe_mul between the two complete adds.
    gl = _check_ladder_loop(
        wloops[0], count=32, width=4, digit_arrays=["db1", "db2"],
        expect_events=["jacobian_double"] * 4
        + ["fe_sub", "jacobian_add_complete",
           "fe_mul", "fe_sub", "jacobian_add_complete"],
        label="GLV ladder", failures=failures)
    if gl:
        gl["beta"] = "fe_mul(Σ TX·onehot, β) precedes only the d2 add"
        facts["glv_ladder"] = gl
    _check_g_loop(wloops[1], "digits0", facts, failures)
    # final join: exactly one add after the loops
    post_jac = [e[0] for e in rec.preamble if e[0] in _JAC_EVENTS]
    if post_jac != ["jacobian_madd_complete", "jacobian_add_complete"]:
        failures.append(f"out-of-loop jacobian events {post_jac} != "
                        "[p-table scan madd, final join add]")
    else:
        facts["join"] = {"final_adds": 1}

    # differential: ±a1 ± λ·a2 must reproduce k, and the eager walk must
    # equal the host's exact a·G + k·P.
    s1 = -a1 if neg1 else a1
    s2 = -a2 if neg2 else a2
    if (s1 + curve_mod.LAMBDA * s2 - k_int) % host.N != 0:
        failures.append("split halves do not recombine to k mod n")
    got = _affine_of(X, Y, Z)
    want = host.G.mul(a_int).add(host.G.mul(k_int)).to_affine()
    if got != want:
        failures.append("differential: eager GLV ladder != a·G + k·P "
                        "(exact host arithmetic)")
    else:
        facts["differential"] = {
            "rule": "eager walk == a·G + (±a1 ± λ·a2)·P == a·G + k·P"}
    f2, fail2 = certify_p_table()
    facts.update(f2)
    failures.extend(fail2)
    if not quick:
        f3, fail3 = certify_g_table()
        facts.update(f3)
        failures.extend(fail3)
    return _finish("curve.double_scalar_mult_glv", facts, failures)


def _pallas_source_checks(facts: Dict[str, Any],
                          failures: List[str]) -> None:
    """AST facts about _kernel_body (and the `_g_select` it calls a
    window) that the eager walk cannot see:
    the one-hot comparands are iota+1 (table row k holds (k+1)·P /
    (j+1)·256^w·G — off-by-one here selects the wrong multiple), and the
    digit signs are XORed with the GLV half signs before negating y."""
    src = "\n".join(textwrap.dedent(inspect.getsource(fn))
                    for fn in (pk_mod._kernel_body, pk_mod._g_select))
    tree = ast.parse(src)
    iota_plus_one = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add)
                and isinstance(node.right, ast.Constant)
                and node.right.value == 1
                and isinstance(node.left, ast.Call)
                and getattr(node.left.func, "attr", "")
                == "broadcasted_iota"):
            dims = [a.value for a in node.left.args[1].elts
                    if isinstance(a, ast.Constant)]
            iota_plus_one.append(tuple(dims))
    if (16, 1, 1, 1) not in iota_plus_one:
        failures.append("pallas: k16 one-hot comparand is not "
                        "broadcasted_iota((16,1,1,1)) + 1 — P-table row k "
                        "holds (k+1)·P, the +1 is load-bearing")
    if (255, 1) not in iota_plus_one:
        failures.append("pallas: k255 comparand is not "
                        "broadcasted_iota((255,1)) + 1")
    sign_xor = any(
        isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitXor)
        for node in ast.walk(tree))
    if not sign_xor:
        failures.append("pallas: digit signs are not XORed with the GLV "
                        "half signs (ds ^ neg)")
    if not failures:
        facts["source"] = {"onehot_comparands": "iota + 1 (k16, k255)",
                           "sign_wiring": "ds_ref[w] ^ neg"}


def _target_pallas_schedule(quick: bool = False) -> CertResult:
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    _pallas_source_checks(facts, failures)

    rec = _Recorder()
    tile = (1, 1)  # one lane: (S, L) behind the rows of every operand
    k_int = 0xBADC0DE0DDF00D0D15EA5E0BEEFFACE0CADFACE0DEAD0FAB0FEED0ACE
    a1, neg1, a2, neg2 = glv_mod.split_lambda(k_int)
    ab1, sb1 = (np.asarray(v) for v in
                pk_mod._signed_digits128(_limb_col(a1, 10)))
    ab2, sb2 = (np.asarray(v) for v in
                pk_mod._signed_digits128(_limb_col(a2, 10)))
    flags = np.zeros((6,) + tile, np.int32)
    flags[0] = host.G_Y & 1         # want_odd
    flags[1] = -1                   # no parity requirement
    flags[3] = 1                    # valid
    flags[4] = 1 if neg1 else 0
    flags[5] = 1 if neg2 else 0
    gx, gy = curve_mod._g_table()

    def lanes(rows, arr=None):
        arr = jnp.zeros((rows, 1), jnp.int32) if arr is None else arr
        return jnp.asarray(arr).reshape((rows,) + tile)

    refs = {
        "px": _FakeRef(lanes(NLIMB, _limb_col(host.G_X)), "px", rec),
        "t1": _FakeRef(lanes(NLIMB), "t1", rec),
        "t1n": _FakeRef(lanes(NLIMB), "t1n", rec),
        "da": _FakeRef(lanes(32), "da", rec),
        "db1": _FakeRef(lanes(pk_mod.SGLV_WINDOWS, ab1), "db1", rec),
        "ds1": _FakeRef(lanes(pk_mod.SGLV_WINDOWS, sb1), "ds1", rec),
        "db2": _FakeRef(lanes(pk_mod.SGLV_WINDOWS, ab2), "db2", rec),
        "ds2": _FakeRef(lanes(pk_mod.SGLV_WINDOWS, sb2), "ds2", rec),
        "flags": _FakeRef(jnp.asarray(flags), "flags", rec),
        "gx": _FakeRef(gx.astype(jnp.float32), "gx", rec),
        "gy": _FakeRef(gy.astype(jnp.float32), "gy", rec),
        "ok": _FakeRef(lanes(2), "ok", rec),
        "tx": _FakeRef(jnp.zeros((16, NLIMB) + tile, jnp.int32), "tx", rec),
        "ty": _FakeRef(jnp.zeros((16, NLIMB) + tile, jnp.int32), "ty", rec),
    }
    patches = _jacobian_spies(rec, pk_mod)
    patches[(jax.lax, "fori_loop")] = _fake_fori(rec)
    patches[(lax, "fori_loop")] = patches[(jax.lax, "fori_loop")]
    try:
        with _Patched(patches):
            pk_mod._kernel_body(
                refs["px"], refs["t1"], refs["t1n"], refs["da"],
                refs["db1"], refs["ds1"], refs["db2"], refs["ds2"],
                refs["flags"], refs["gx"], refs["gy"], refs["ok"],
                refs["tx"], refs["ty"])
    except Exception as e:  # noqa: BLE001
        failures.append(f"ledger walk: {type(e).__name__}: {e}")
        return _finish("pallas.kernel_schedule", facts, failures)

    # -- table build: object-flow chain proof ---------------------------
    # write tx[0] = P; row 1 = double(P); row k (2..15) = row k−1 + P via
    # flagged ratio-madds whose base args are the SAME objects every time
    # and whose (X,Y,Z) inputs are the previous call's outputs — so row k
    # holds the chain value (k+1)·P by induction.
    pre = rec.preamble
    jac = [(n, m) for n, m in pre if n in _JAC_EVENTS]
    names = [n for n, _ in jac]
    if names != (["jacobian_double"]
                 + ["jacobian_madd_flagged_ratio"] * 14
                 + ["jacobian_add_flagged"]):
        failures.append(f"pallas: out-of-loop jacobian events {names} != "
                        "[table double, 14 ratio madds, final join]")
    else:
        dbl_meta = jac[0][1]
        ratio_meta = [m for _, m in jac[1:15]]
        base = dbl_meta["in"][:2] if isinstance(dbl_meta, dict) else None
        chain_ok = base is not None
        prev_out = dbl_meta["out"][:3] if chain_ok else None
        for m in ratio_meta:
            if not isinstance(m, dict) or m["in"][3:5] != base or \
                    m["in"][:3] != prev_out:
                chain_ok = False
                break
            prev_out = m["out"][:3]
        writes = [(meta[0]) for n, meta in pre if n == "write:tx"
                  and isinstance(meta, tuple)]
        if writes[:16] != list(range(16)):
            failures.append(f"pallas: table rows written in order "
                            f"{writes[:16]}, expected 0..15")
        elif not chain_ok:
            failures.append("pallas: table build is not a single-base "
                            "madd chain — row k is not (k+1)·P")
        else:
            facts["table"] = {"rows": 16,
                              "rule": "row k == (k+1)·P (object-flow "
                                      "chain: double + 14 madds of the "
                                      "same base)"}

    wloops = _window_loops(rec)
    if len(wloops) != 2:
        failures.append(f"pallas: found {len(wloops)} jacobian window "
                        "loops, expected 2 (signed GLV + G loop)")
        return _finish("pallas.kernel_schedule", facts, failures)
    wl = _check_ladder_loop(
        wloops[0], count=pk_mod.SGLV_WINDOWS, width=pk_mod.SGLV_WIDTH,
        digit_arrays=["db1", "db2"],
        expect_events=["jacobian_double"] * 5
        + ["fe_sub", "jacobian_madd_flagged",
           "fe_mul", "fe_sub", "jacobian_madd_flagged"],
        label="pallas signed ladder", failures=failures)
    if wl:
        # signs must be read in lockstep with the digits
        for i in range(pk_mod.SGLV_WINDOWS):
            it = wloops[0]["iters"][i]
            w = pk_mod.SGLV_WINDOWS - 1 - i
            sreads = [idx for arr, idx in it["reads"]
                      if arr in ("ds1", "ds2")]
            if sreads != [w, w]:
                failures.append(f"pallas: iteration {i} sign reads "
                                f"{sreads} != [{w}, {w}]")
                wl = {}
                break
    if wl:
        wl["beta"] = "fe_mul(Σ TX·onehot, β) precedes only the d2 madd"
        facts["signed_ladder"] = wl
    gl = wloops[1]
    if (gl["lo"], gl["hi"]) != (0, 32) or not gl.get("complete"):
        failures.append("pallas: G loop bounds/completeness wrong")
    else:
        ok = True
        for i in range(32):
            it = gl["iters"][i]
            if [e[0] for e in it["events"]] != ["jacobian_madd_flagged"]:
                failures.append(f"pallas: G loop iteration {i} schedule "
                                f"{[e[0] for e in it['events']]}")
                ok = False
                break
            reads = [idx for arr, idx in it["reads"]
                     if arr in ("da", "gx", "gy")]
            if reads != [i, i, i]:
                failures.append(f"pallas: G loop iteration {i} reads "
                                f"{reads}, expected window {i} of "
                                "da/gx/gy")
                ok = False
                break
        if ok:
            facts["g_loop"] = {"windows": 32, "doubles_per_window": 0,
                               "order": "ascending, table row "
                                        "(j+1)·256^w·G"}
    if not quick:
        f3, fail3 = certify_g_table()
        facts.update(f3)
        failures.extend(fail3)
    return _finish("pallas.kernel_schedule", facts, failures)


# --------------------------------------------------------------------------
# target registry / public API
# --------------------------------------------------------------------------

TARGETS: Dict[str, Callable[..., CertResult]] = {
    "scalar._digits": lambda quick=False: _target_digits(),
    "scalar._digits128": lambda quick=False: _target_digits128(),
    "scalar.bytes_to_limbs": lambda quick=False: _target_bytes_to_limbs(),
    "sha256.bytes_from_words":
        lambda quick=False: _target_bytes_from_words(),
    "scalar._signed_digits128":
        lambda quick=False: _target_signed_digits128(),
    "glv.split_lambda": lambda quick=False: _target_glv(),
    "curve.double_scalar_mult_glv": _target_double_scalar_mult_glv,
    "pallas.kernel_schedule": _target_pallas_schedule,
}

# Function names host_lint's scalar-coverage rule accepts as "registered
# with the schedule prover" (mapped to the target that certifies them).
REGISTERED_RECODERS: Dict[str, str] = {
    "scalar_bits": "scalar._digits",
    "_digits": "scalar._digits",
    "_digits128": "scalar._digits128",
    "_signed_digits128": "scalar._signed_digits128",
    "bytes_to_limbs": "scalar.bytes_to_limbs",
    "int_to_limbs": "scalar.bytes_to_limbs",
    "limbs_to_int": "scalar.bytes_to_limbs",
    "_bytes_from_words": "sha256.bytes_from_words",
    "ints_to_limbs_batch": "scalar._signed_digits128",
    "split_lambda": "glv.split_lambda",
    "double_scalar_mult_glv": "curve.double_scalar_mult_glv",
    "_fixed_base_mult": "curve.double_scalar_mult_glv",
    "_p_table": "curve.double_scalar_mult_glv",
    "_kernel_body": "pallas.kernel_schedule",
}


# Targets whose certificate needs an eager ledger walk (~1-2 min each on
# CPU); the stats mini-workload and test suite certify only the fast set,
# CI's --schedule leg runs everything.
HEAVY_TARGETS = {
    "curve.double_scalar_mult_glv",
    "pallas.kernel_schedule",
}


def all_targets(include_heavy: bool = True) -> List[str]:
    names = list(TARGETS)
    if not include_heavy:
        names = [n for n in names if n not in HEAVY_TARGETS]
    return names


def certify(name: str, quick: bool = False) -> CertResult:
    try:
        return TARGETS[name](quick=quick)
    except Exception as e:  # noqa: BLE001 — unevaluable is FAIL
        return CertResult(name, "FAIL", {},
                          [f"{type(e).__name__}: {e}"])


_CERT_COUNTER = None


def certify_all(quick: bool = False,
                emit_metrics: bool = True,
                include_heavy: bool = True) -> List[CertResult]:
    global _CERT_COUNTER
    results = [certify(n, quick=quick)
               for n in all_targets(include_heavy=include_heavy)]
    if emit_metrics:
        if _CERT_COUNTER is None:
            from ..obs import counter
            _CERT_COUNTER = counter(
                "consensus_scalar_certificates",
                "Scalar-schedule prover certificates by target and status",
                ("target", "status"))
        for r in results:
            _CERT_COUNTER.inc(target=r.name, status=r.status)
    return results


# --------------------------------------------------------------------------
# planted-unsound negatives — the prover must REJECT every one
# --------------------------------------------------------------------------

def _toy_bad_weights_recoder() -> CertResult:
    """Out-of-range digit: weights [1, 2, 4, 9] instead of [1, 2, 4, 8]
    — windows can exceed 2^width − 1 and recombination is broken."""

    def bad_digits(limbs):
        bits = curve_mod.scalar_bits(limbs)[:256]
        b = bits.reshape((64, 4) + limbs.shape[1:])
        weights = jnp.asarray([1, 2, 4, 9], dtype=jnp.int32).reshape(
            (1, 4) + (1,) * (limbs.ndim - 1))
        return jnp.sum(b * weights, axis=1)

    facts: Dict[str, Any] = {}
    failures: List[str] = []
    _prove_digit_slices("toy_bad_weights", bad_digits,
                        _seed_limb_bits(NLIMB), 64, 4, facts, failures)
    return _finish("negative.scalar-digit-range", facts, failures)


def _toy_bad_carry() -> CertResult:
    """Wrong carry fold: digit = t − 31 on carry instead of t − 32 —
    the telescoping invariant (and hence reconstruction) breaks."""
    return prove_carry_automaton(
        step_fn=lambda t: ((1 if t >= 16 else 0),
                           t - 31 * (1 if t >= 16 else 0)))


def _toy_ladder(order_desc: bool, doubles: int) -> CertResult:
    """4-window width-2 ladder over an 8-bit scalar using the production
    jacobian ops and table; run through the SAME generic ledger check as
    the real ladders.  order_desc=True, doubles=2 is the sound schedule
    (checker self-test); ascending order or doubles != width must FAIL."""
    facts: Dict[str, Any] = {}
    failures: List[str] = []
    rec = _Recorder()
    scalar = 0b10110110
    px, py = _limb_col(host.G_X), _limb_col(host.G_Y)
    digits = _SpyArray(
        jnp.asarray([[(scalar >> (2 * w)) & 3] for w in range(4)],
                    jnp.int32), "digits0", rec)

    def ladder():
        TX, TY, TZ = curve_mod._p_table(px, py)
        k4 = jnp.arange(4, dtype=jnp.int32).reshape((4,) + (1,) * px.ndim)

        def body(i, R):
            w = (3 - i) if order_desc else i
            for _ in range(doubles):
                R = curve_mod.jacobian_double(*R)
            d = digits[w]
            oh = (d[None] == k4).astype(jnp.int32)
            selx = jnp.sum(TX[:4] * oh, axis=0)
            sely = jnp.sum(TY[:4] * oh, axis=0)
            selz = jnp.sum(TZ[:4] * oh, axis=0)
            return curve_mod.jacobian_add_complete(
                *R, selx, sely, selz, d == 0)

        return lax.fori_loop(0, 4, body, curve_mod._inf_like(px))

    patches = _jacobian_spies(rec, curve_mod)
    patches[(jax.lax, "fori_loop")] = _fake_fori(rec)
    patches[(lax, "fori_loop")] = patches[(jax.lax, "fori_loop")]
    try:
        with _Patched(patches):
            R = ladder()
    except Exception as e:  # noqa: BLE001
        failures.append(f"toy ladder walk: {type(e).__name__}: {e}")
        return _finish("negative.toy-ladder", facts, failures)
    wloops = _window_loops(rec)
    if len(wloops) != 1:
        failures.append(f"toy ladder: {len(wloops)} window loops")
        return _finish("negative.toy-ladder", facts, failures)
    led = _check_ladder_loop(
        wloops[0], count=4, width=2, digit_arrays=["digits0"],
        expect_events=["jacobian_double"] * doubles
        + ["jacobian_add_complete"],
        label="toy ladder", failures=failures)
    if led:
        facts["toy_ladder"] = led
    got = _affine_of(*R[:3])
    if got != host.G.mul(scalar).to_affine():
        failures.append("toy ladder differential: result != scalar·P")
    elif led:
        facts["differential"] = {"rule": "toy walk == scalar·P"}
    return _finish("negative.toy-ladder", facts, failures)


def _cert_to_report(name: str, cert: CertResult) -> interval.Report:
    rep = interval.Report(name=f"negative.{name}", ok=cert.ok)
    for f in cert.failures:
        rep.violations.append(
            interval.Violation(kind="schedule", where=cert.name, msg=f))
    rep.notes.append(f"scalar-schedule prover verdict: {cert.status}")
    return rep


NEGATIVES: Dict[str, Callable[[], CertResult]] = {
    "scalar-carry-fold": _toy_bad_carry,
    "scalar-window-order": lambda: _toy_ladder(order_desc=False,
                                               doubles=2),
    "scalar-dropped-doubling": lambda: _toy_ladder(order_desc=True,
                                                   doubles=1),
    "scalar-digit-range": _toy_bad_weights_recoder,
    "scalar-glv-constant": lambda: prove_glv_constants(
        B2=glv_mod._B2 + 2),
}


def toy_ladder_selftest() -> CertResult:
    """The sound toy schedule must PASS through the same checker the
    negatives fail — proves the gate is alive, not trivially rejecting."""
    return _toy_ladder(order_desc=True, doubles=2)


def analyze_negative(name: str) -> interval.Report:
    return _cert_to_report(name, NEGATIVES[name]())
