"""Interval abstract interpretation + determinism gate over closed jaxprs.

The whole TPU design rests on one claim: every intermediate of the
radix-2^13 field pipeline fits a signed int32 lane. `ops/limbs.py` tracks
that claim by hand — static Python-int `Bounds` lists threaded alongside
the traced arrays, asserted by the same code they audit. This module is
the *independent* auditor: it closes the jaxpr of a consensus kernel and
re-derives per-element integer intervals for every equation, with no
access to the hand bookkeeping.

The theorem proved per kernel (the "observation discipline"):

  XLA int32 add/sub/mul/shift-left are exact mod 2^32 (two's-complement
  wrap), i.e. ring homomorphisms on residues. So a signed value's TRUE
  (unbounded-integer) interval propagates exactly through ring ops even
  if the machine representation transiently wraps — the Karatsuba
  sum-convolution in `fe_mul` relies on exactly this. Wrapping only
  corrupts math at *observing* ops whose result is not a residue
  function: right shifts, comparisons, div/min/max, int<->float
  converts, and the kernel outputs. At every such observation the
  analyzer demands the operand's true interval fit the lane
  ([-2^31, 2^31) for int32); a kernel is overflow-free iff no
  observation fails. Unsigned dtypes (SHA-256) wrap by *spec*: every
  unsigned op is a residue function, so their intervals are reduced
  mod 2^w and never violate.

Precision machinery (needed to prove the real kernels, not toys):

- Intervals are tracked per-row along the first TWO axes (capped at
  `ROW_CAP`), collapsed elsewhere. Axis 0 is the limb axis in this
  codebase, so the derived rows are directly comparable to the
  hand-tracked `Bounds` lists (tests pin them equal).
- One-hot selects: `(digit == iota_rows)` yields an at-most-one-nonzero-
  along-axis-0 flag; `reduce_sum(table * onehot, axis=0)` then joins
  rows instead of summing them — without this the windowed scalar-mult
  table selects false-alarm by a factor of the table size.
- Exact-float discipline: every float32 value carries an exactness
  CERTIFICATE (exactf + a tracked magnitude bound <= 2^24, exact in an
  f32 mantissa), propagated end to end through converts, one-hot
  construction, select_n, mul/add, reductions and HIGHEST-precision
  dots — where the sound rule is the ACCUMULATED bound
  Sum|terms| <= 2^24, not the result hull. A primitive outside
  FLOAT_VETTED demotes the certificate with a sourced diagnostic
  (`fwhy`), and an inexact f32 reaching a use site or a kernel output
  is a violation. Every float equation is appended to the report's
  `exactness` trace — the machine-checkable theorem the MXU one-hot
  fe_mul candidate and the gtable selects are certified by.
- Loops: `scan` (what `fori_loop` lowers to) and fori-shaped `while`
  run to an abstract fixpoint with staged widening; `while` with a
  data-dependent trip count is rejected outright (determinism gate).

The determinism/allowlist gate piggybacks on the same walk: any
primitive without a registered transfer rule, any 64-bit dtype, any
non-exact float, and any non-fori `while` is reported. The allowlist IS
the transfer registry — a primitive we cannot bound is a primitive we
do not allow in consensus kernels.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jax_core

__all__ = [
    "AbstractArray",
    "Report",
    "Violation",
    "analyze",
    "analyze_closed",
    "ALLOWED_PRIMITIVES",
    "FLOAT_VETTED",
]

# Saturation sentinel: "unbounded" true value. Big enough that no real
# kernel bound reaches it; arithmetic on it stays exact Python-int math.
INF = 1 << 300
ROW_CAP = 64  # track per-row intervals along axes whose size is <= this
EXACT_F32 = 1 << 24  # integers up to 2^24 are exact in a float32 mantissa

# Dense power-of-two stages: each widening step jumps a carry bound to
# the next stage. The 2^14 stage matters: the W2 weak-representation rows
# (max 15631) live between 2^13 and 2^14, and a coarser ladder would
# overshoot point-coordinate carries past the region where the field ops
# are contracting, never to return.
_WIDEN_HI = [0, 1] + [(1 << k) - 1 for k in range(13, 32)] + [INF]
_WIDEN_LO = [0, -1] + [-(1 << k) for k in range(13, 32)] + [-INF]
_MAX_FIX_ITERS = 24


def _sat(v: int) -> int:
    return INF if v > INF else (-INF if v < -INF else v)


def _hull(a: Tuple[int, int], b: Tuple[int, int]) -> Tuple[int, int]:
    return (a[0] if a[0] < b[0] else b[0], a[1] if a[1] > b[1] else b[1])


def _widen_cell(old: Tuple[int, int], new: Tuple[int, int]) -> Tuple[int, int]:
    lo, hi = new
    if hi > old[1]:
        hi = next(t for t in _WIDEN_HI if t >= hi)
    if lo < old[0]:
        lo = next(t for t in _WIDEN_LO if t <= lo)
    return (lo, hi)


def _dkind(dtype) -> Tuple[str, int]:
    d = np.dtype(dtype)
    if d == np.bool_:
        return ("bool", 1)
    return ({"i": "int", "u": "uint", "f": "float"}.get(d.kind, "other"),
            d.itemsize * 8)


class AbstractArray:
    """Interval abstraction of one array: per-cell (lo, hi) true-value
    bounds over a (r0, r1) grid covering the first two axes (rX is 1 when
    that axis is collapsed/joined), plus relational flags.

    nz0: along axis 0, at most one element is nonzero (per fixed index of
         the remaining axes) — the one-hot/masked-select property.
    uni0: the value is constant along axis 0.
    dist0: every axis-0 row is a constant, and the row constants are
           pairwise distinct (an iota/table-key property that survives
           past ROW_CAP, where per-row cells can no longer express it).
    exactf: float dtype carrying exactly-representable integers
            (|v| <= 2^24); non-exact floats are violations at use sites.
    fwhy: for a float value with exactf=False, the sourced reason the
          exactness certificate was lost (the demoting equation). None
          for exact floats and non-floats. Carried so the eventual
          violation (at a use site or the kernel output) can name the
          equation that actually broke the chain, not just the symptom.
    """

    __slots__ = ("shape", "dtype", "cells", "nz0", "uni0", "dist0",
                 "exactf", "fwhy", "poly", "cong")

    def __init__(self, shape, dtype, cells, nz0=False, uni0=False,
                 exactf=False, dist0=False, poly=None, fwhy=None,
                 cong=None):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.cells = cells  # list[r0] of list[r1] of (lo, hi)
        self.nz0 = nz0
        self.uni0 = uni0
        self.dist0 = dist0
        self.exactf = exactf
        self.fwhy = fwhy
        # Optional sum-of-products refinement (see _poly_transfer): dict
        # monomial -> {row_or_None: int coeff}. Sound per-cell true-value
        # decomposition over interval atoms; used to recover correlations
        # interval arithmetic loses (the Karatsuba z1 = S - z0 - z2).
        self.poly = poly
        # Optional congruence facts (see _cong_transfer): a list of
        # per-axis-0-row facts, each None or a pair (m, r) meaning every
        # element of that row satisfies x ≡ r (mod m); m == 0 means the
        # row is EXACTLY r (the zero modulus is the whole-integer-kills-
        # everything convention: gcd(0, m) == m makes the join uniform).
        # Length is 1 (a fact uniform over all rows) or shape[0]. None
        # means no fact — always sound to drop, which is what widening
        # and every unsupported transfer do.
        self.cong = cong

    @property
    def r0(self) -> int:
        return len(self.cells)

    @property
    def r1(self) -> int:
        return len(self.cells[0])

    def cell(self, i: int, j: int) -> Tuple[int, int]:
        return self.cells[i if len(self.cells) > 1 else 0][
            j if len(self.cells[0]) > 1 else 0
        ]

    def joined(self) -> Tuple[int, int]:
        lo = min(c[0] for row in self.cells for c in row)
        hi = max(c[1] for row in self.cells for c in row)
        return (lo, hi)

    def rows0(self) -> List[Tuple[int, int]]:
        """Per-axis-0 row hulls, expanded to shape[0] entries."""
        n = self.shape[0] if self.shape else 1
        out = []
        for i in range(n):
            lo = min(self.cell(i, j)[0] for j in range(max(self.r1, 1)))
            hi = max(self.cell(i, j)[1] for j in range(max(self.r1, 1)))
            out.append((lo, hi))
        return out

    def same_as(self, other: "AbstractArray") -> bool:
        return (self.cells == other.cells and self.nz0 == other.nz0
                and self.uni0 == other.uni0 and self.exactf == other.exactf
                and self.dist0 == other.dist0)

    def __repr__(self):
        return (f"AbstractArray({self.shape}, {self.dtype.name}, "
                f"r=({self.r0},{self.r1}), hull={self.joined()})")


def _grid_dims(shape) -> Tuple[int, int]:
    g0 = shape[0] if len(shape) >= 1 and 1 < shape[0] <= ROW_CAP else 1
    g1 = shape[1] if len(shape) >= 2 and 1 < shape[1] <= ROW_CAP else 1
    return g0, g1


def _collapse_if_uniform(cells):
    if len(cells) > 1 and all(r == cells[0] for r in cells[1:]):
        cells = [cells[0]]
    if len(cells[0]) > 1 and all(
        all(c == row[0] for c in row[1:]) for row in cells
    ):
        cells = [[row[0]] for row in cells]
    return cells


def mk(shape, dtype, cells, nz0=False, uni0=False, exactf=False,
       dist0=False):
    """Normalize + build: saturate, reduce unsigned mod 2^w, clamp bool,
    collapse uniform grids (perf: most values are batch-uniform)."""
    kind, bits = _dkind(dtype)
    out = []
    for row in cells:
        r = []
        for lo, hi in row:
            lo, hi = _sat(lo), _sat(hi)
            if kind == "uint":
                m = 1 << bits
                if hi - lo >= m:
                    lo, hi = 0, m - 1
                else:
                    lo2 = lo % m
                    hi2 = lo2 + (hi - lo)
                    lo, hi = (0, m - 1) if hi2 >= m else (lo2, hi2)
            elif kind == "bool":
                lo, hi = max(lo, 0), min(hi, 1)
            r.append((lo, hi))
        out.append(r)
    out = _collapse_if_uniform(out)
    if len(shape) >= 1 and shape[0] == 1:
        uni0 = True
    return AbstractArray(shape, dtype, out, nz0=nz0, uni0=uni0,
                         exactf=exactf, dist0=dist0)


def full_range(shape, dtype) -> AbstractArray:
    kind, bits = _dkind(dtype)
    if kind == "int":
        c = (-(1 << (bits - 1)), (1 << (bits - 1)) - 1)
    elif kind == "uint":
        c = (0, (1 << bits) - 1)
    elif kind == "bool":
        c = (0, 1)
    else:
        c = (-INF, INF)
    return mk(shape, dtype, [[c]])


def top(shape, dtype) -> AbstractArray:
    """Unknown TRUE value (post-violation recovery): unbounded."""
    return AbstractArray(shape, dtype, [[(-INF, INF)]])


def from_concrete(arr) -> AbstractArray:
    """Abstract a concrete array (jaxpr consts / literals) exactly, with
    per-row mins/maxes along the tracked axes."""
    a = np.asarray(arr)
    kind, _ = _dkind(a.dtype)
    exactf = False
    if kind == "float":
        finite = bool(np.all(np.isfinite(a)))
        integral = finite and bool(np.all(a == np.trunc(a)))
        small = finite and (a.size == 0 or float(np.max(np.abs(a))) <= EXACT_F32)
        exactf = integral and small
        to_int = (lambda v: int(v)) if exactf else (lambda v: int(np.floor(v)))
    else:
        to_int = int
    if a.size == 0:
        return mk(a.shape, a.dtype, [[(0, 0)]], exactf=exactf)
    g0, g1 = _grid_dims(a.shape)
    cells = []
    for i in range(g0):
        sl0 = a[i] if g0 > 1 else a
        row = []
        for j in range(g1):
            sl = (sl0[j] if g0 > 1 else sl0[:, j]) if g1 > 1 else sl0
            row.append((to_int(np.min(sl)), to_int(np.max(sl))))
        cells.append(row)
    uni0 = bool(a.ndim >= 1 and a.shape[0] >= 1
                and np.all(a == a[:1]))
    dist0 = False
    if a.ndim >= 1 and a.shape[0] > 1 and kind != "float":
        flat = a.reshape(a.shape[0], -1)
        row_lo, row_hi = flat.min(axis=1), flat.max(axis=1)
        dist0 = bool(np.all(row_lo == row_hi)
                     and len(np.unique(row_lo)) == a.shape[0])
    av = mk(a.shape, a.dtype, cells, uni0=uni0, exactf=exactf,
            dist0=dist0)
    if kind != "float":
        # Congruence seeding: a constant row is exactly its value (m=0).
        rows = [((0, row[0][0]) if all(lo == hi and lo == row[0][0]
                                       for lo, hi in row) else None)
                for row in cells]
        if any(f is not None for f in rows):
            av.cong = rows
    return av


@dataclass
class Violation:
    kind: str      # overflow | float | allowlist | dtype64 | loop | internal
                   # | grid | ref | vmem (Pallas layer, pallas_check.py)
    where: str     # eqn path, e.g. "scan[3].body.eqn[17] mul"
    msg: str

    def __str__(self):
        return f"[{self.kind}] {self.where}: {self.msg}"


@dataclass
class Report:
    name: str
    ok: bool = True
    violations: List[Violation] = field(default_factory=list)
    prim_counts: Dict[str, int] = field(default_factory=dict)
    n_eqns: int = 0
    out_bounds: List[List[Tuple[int, int]]] = field(default_factory=list)
    wrap_eqns: int = 0      # signed ring ops whose interval left int32
    max_observed: int = 0   # largest |bound| proven at an observation
    notes: List[str] = field(default_factory=list)
    # Exact-float theorem trace: one entry per float-dtyped equation
    # output (unmuted passes), recording the primitive, the proven
    # magnitude bound, whether the exactness certificate survived, and —
    # for dot_general / reduce_sum — the accumulated sum-of-|terms|
    # bound actually checked against 2^24. This is the machine-checkable
    # per-value bound trace the report JSON exports.
    exactness: List[dict] = field(default_factory=list)
    # Congruence facts proven for each kernel output: one list per
    # output, one entry per axis-0 row, each None or (m, r) meaning
    # every element of that row is ≡ r (mod m) (m == 0: exactly r).
    out_cong: List[List[Optional[Tuple[int, int]]]] = field(
        default_factory=list)
    # Pallas-layer facts (analysis/pallas_check.py): peak VMEM live set
    # of the kernel (blocks + scratch + intermediates) and the grid shape.
    vmem_peak_bytes: Optional[int] = None
    grid: Optional[Tuple[int, ...]] = None

    def to_dict(self) -> dict:
        def b(v):  # saturated bounds -> JSON-safe
            return "unbounded" if abs(v) >= INF else int(v)

        d = {
            "kernel": self.name,
            "ok": self.ok,
            "violations": [
                {"kind": v.kind, "where": v.where, "msg": v.msg}
                for v in self.violations
            ],
            "n_eqns": self.n_eqns,
            "prim_counts": dict(sorted(self.prim_counts.items())),
            "wrap_eqns": self.wrap_eqns,
            "max_observed": b(self.max_observed),
            "out_bounds": [
                [[b(lo), b(hi)] for lo, hi in rows] for rows in self.out_bounds
            ],
            "notes": self.notes,
        }
        if self.exactness:
            d["exactness"] = self.exactness
        if any(any(f is not None for f in rows) for rows in self.out_cong):
            d["out_cong"] = [
                [None if f is None else [int(f[0]), int(f[1])]
                 for f in rows]
                for rows in self.out_cong
            ]
        if self.vmem_peak_bytes is not None:
            d["vmem_peak_bytes"] = int(self.vmem_peak_bytes)
        if self.grid is not None:
            d["grid"] = [int(g) for g in self.grid]
        return d


_TRACE_CAP = 4096  # exactness-trace entries per report (overflow noted)


class _Ctx:
    def __init__(self, report: Report):
        self.report = report
        self.mute = 0  # >0 during fixpoint warmup iterations
        # >0 while evaluating a loop body (any _fixpoint pass, including
        # the final unmuted one) or a multi-branch cond. Stateful rules
        # (the Ref writes of analysis/pallas_check.py) must downgrade
        # strong updates to hull-merges here: the body may abstract more
        # than one concrete execution.
        self.in_loop = 0
        # Scratchpad cleared before each equation: transfer rules drop
        # facts here (e.g. dot_general's accumulated sum bound) and the
        # float post-pass folds them into the exactness-trace entry.
        self.eqn_facts: Dict[str, object] = {}

    def violate(self, kind: str, where: str, msg: str):
        if self.mute:
            return
        self.report.ok = False
        self.report.violations.append(Violation(kind, where, msg))

    def trace_float(self, entry: dict):
        if self.mute:
            return
        tr = self.report.exactness
        if len(tr) >= _TRACE_CAP:
            if len(tr) == _TRACE_CAP:
                tr.append({"note": f"exactness trace capped at "
                                   f"{_TRACE_CAP} entries"})
            return
        tr.append(entry)

    def note_wrap(self):
        if not self.mute:
            self.report.wrap_eqns += 1

    def observe(self, av: AbstractArray, where: str, what: str) -> AbstractArray:
        """Demand av's true interval fit its (signed) lane; unsigned and
        bool are residue-defined and always pass. Returns a clamped value
        so one failure does not cascade into noise."""
        kind, bits = _dkind(av.dtype)
        if kind not in ("int",):
            return av
        lo_l, hi_l = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
        bad = None
        for row in av.cells:
            for lo, hi in row:
                if lo < lo_l or hi > hi_l:
                    bad = (lo, hi)
                    break
                m = max(abs(lo), abs(hi))
                if not self.mute and m > self.report.max_observed:
                    self.report.max_observed = m
            if bad:
                break
        if bad is None:
            return av
        lo, hi = bad

        def s(v):
            return "unbounded" if abs(v) >= INF else str(v)

        self.violate(
            "overflow", where,
            f"{what}: derived interval [{s(lo)}, {s(hi)}] exceeds "
            f"int{bits} lane range [{lo_l}, {hi_l}]",
        )
        cells = [[(max(lo2, lo_l), min(hi2, hi_l)) for lo2, hi2 in row]
                 for row in av.cells]
        return AbstractArray(av.shape, av.dtype, cells, nz0=av.nz0,
                             uni0=av.uni0)


# ---------------------------------------------------------------------------
# Grid utilities.

def _aligned_cells(a: AbstractArray, b: AbstractArray):
    """Iterate aligned (r0, r1) cell grids of two same-result-shape values
    (operand grids may be 1 where the other tracks rows)."""
    r0 = max(a.r0, b.r0)
    r1 = max(a.r1, b.r1)
    return r0, r1


def _ewise(ctx, shape, dtype, ops: Sequence[AbstractArray],
           f: Callable[..., Tuple[int, int]], **flags) -> AbstractArray:
    r0 = max(o.r0 for o in ops)
    r1 = max(o.r1 for o in ops)
    cells = [
        [f(*(o.cell(i, j) for o in ops)) for j in range(r1)]
        for i in range(r0)
    ]
    return mk(shape, dtype, cells, **flags)


def take_axes(av: AbstractArray, shape, a0: Optional[int],
              a1: Optional[int], **flags) -> AbstractArray:
    """Rebuild a grid for a result whose axis 0 comes from operand axis
    `a0` and axis 1 from `a1` (None = no tracked source: join). Joins over
    whichever tracked operand axes are not referenced."""

    def src_rows(ax):
        if ax == 0 and av.r0 > 1:
            return [
                ( min(c[0] for c in row), max(c[1] for c in row) )
                for row in av.cells
            ], av.r0
        if ax == 1 and av.r1 > 1:
            return [
                (
                    min(av.cells[i][j][0] for i in range(av.r0)),
                    max(av.cells[i][j][1] for i in range(av.r0)),
                )
                for j in range(av.r1)
            ], av.r1
        return None, 1

    if (a0 == 0 and a1 == 1) and av.r0 >= 1:
        cells = av.cells
    elif (a0 == 1 and a1 == 0):
        cells = [
            [av.cells[i][j] for i in range(av.r0)] for j in range(av.r1)
        ]
    else:
        rows_a, _ = src_rows(a0)
        rows_b, _ = src_rows(a1) if a1 is not None else (None, 1)
        if rows_a is not None and rows_b is None:
            cells = [[c] for c in rows_a]
        elif rows_a is None and rows_b is not None:
            cells = [rows_b]
        elif rows_a is not None and rows_b is not None:
            # Both requested axes tracked but the cross-cells unknown:
            # every element of result cell (i, j) lies in BOTH source-row
            # hulls, so the intersection is sound (non-empty for any cell
            # that abstracts a real element; hull as a safe fallback).
            cells = [
                [
                    (max(ra[0], rb[0]), min(ra[1], rb[1]))
                    if max(ra[0], rb[0]) <= min(ra[1], rb[1])
                    else _hull(ra, rb)
                    for rb in rows_b
                ]
                for ra in rows_a
            ]
        else:
            cells = [[av.joined()]]
    flags.setdefault("exactf", av.exactf)
    return mk(shape, av.dtype, cells, **flags)


def join_values(a: AbstractArray, b: AbstractArray) -> AbstractArray:
    r0 = max(a.r0, b.r0)
    r1 = max(a.r1, b.r1)
    cells = [
        [_hull(a.cell(i, j), b.cell(i, j)) for j in range(r1)]
        for i in range(r0)
    ]
    out = AbstractArray(
        a.shape, a.dtype, _collapse_if_uniform(cells),
        nz0=a.nz0 and b.nz0, uni0=a.uni0 and b.uni0,
        exactf=a.exactf and b.exactf,
    )
    if a.cong is not None and b.cong is not None:
        n = max(len(a.cong), len(b.cong))
        ra, rb = _cong_expand(a.cong, n), _cong_expand(b.cong, n)
        rows = [_cong_join(fa, fb) for fa, fb in zip(ra, rb, strict=True)]
        if any(f is not None for f in rows):
            out.cong = rows
    return out


# ---------------------------------------------------------------------------
# Transfer rules. RULES maps primitive name -> fn(interp, eqn, ins, where)
# -> list of AbstractArray. The keys double as the op allowlist.

RULES: Dict[str, Callable] = {}


def _rule(*names):
    def deco(fn):
        for n in names:
            RULES[n] = fn
        return fn
    return deco


def _out_aval(eqn, i=0):
    return eqn.outvars[i].aval


def _is_signed(av: AbstractArray) -> bool:
    return _dkind(av.dtype)[0] == "int"


def _int32_ok(cell: Tuple[int, int], bits: int) -> bool:
    return cell[0] >= -(1 << (bits - 1)) and cell[1] <= (1 << (bits - 1)) - 1


def _check_float_exact(interp, where, ops, result_cells_hull):
    """Shared float-policy check for arithmetic combining floats."""
    bad = next((o for o in ops
                if _dkind(o.dtype)[0] == "float" and not o.exactf), None)
    if bad is not None:
        why = f" [{bad.fwhy}]" if bad.fwhy else ""
        interp.ctx.violate(
            "float", where,
            "float operand without exact-integer provenance "
            f"(only int->f32 converts of values |v| <= 2^24 are vetted){why}",
        )
        return False
    lo, hi = result_cells_hull
    if max(abs(lo), abs(hi)) > EXACT_F32:
        interp.ctx.violate(
            "float", where,
            f"float result interval [{lo}, {hi}] exceeds the 2^24 "
            "exact-integer range of a float32 mantissa",
        )
        return False
    return True


@_rule("add", "sub", "mul")
def _r_arith(interp, eqn, ins, where):
    a, b = ins
    out = _out_aval(eqn)
    name = eqn.primitive.name

    if name == "add":
        f = lambda x, y: (x[0] + y[0], x[1] + y[1])  # noqa: E731
    elif name == "sub":
        f = lambda x, y: (x[0] - y[1], x[1] - y[0])  # noqa: E731
    else:
        def f(x, y):
            ps = (x[0] * y[0], x[0] * y[1], x[1] * y[0], x[1] * y[1])
            return (min(ps), max(ps))

    nz0 = name == "mul" and (a.nz0 or b.nz0)
    # Adding/subtracting a single constant shifts every row by the same
    # amount: distinct constant rows stay distinct constant rows. (The
    # Pallas G-loop builds its one-hot key as `broadcasted_iota + 1`,
    # which must keep dist0 past ROW_CAP or the MXU select false-alarms.)
    dist0 = False
    if name in ("add", "sub"):
        ja, jb = a.joined(), b.joined()
        dist0 = ((a.dist0 and jb[0] == jb[1])
                 or (b.dist0 and ja[0] == ja[1]))
    res = _ewise(interp.ctx, out.shape, out.dtype, ins, f,
                 nz0=nz0, uni0=a.uni0 and b.uni0, dist0=dist0)
    kind, bits = _dkind(out.dtype)
    if kind == "float":
        ok = _check_float_exact(interp, where, ins, res.joined())
        res.exactf = ok
    elif kind == "int":
        if not all(_int32_ok(c, bits) for row in res.cells for c in row):
            interp.ctx.note_wrap()  # transient wrap: legal for ring ops
    return [res]


@_rule("neg")
def _r_neg(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    res = _ewise(interp.ctx, out.shape, out.dtype, ins,
                 lambda x: (-x[1], -x[0]), uni0=a.uni0)
    if _dkind(out.dtype)[0] == "float":
        res.exactf = _check_float_exact(interp, where, ins, res.joined())
    return [res]


def _up2m1(v: int) -> int:
    """Smallest 2^k - 1 >= v (for nonneg v)."""
    return (1 << max(v, 0).bit_length()) - 1


@_rule("and", "or", "xor")
def _r_bitwise(interp, eqn, ins, where):
    a, b = ins
    out = _out_aval(eqn)
    kind, bits = _dkind(out.dtype)
    name = eqn.primitive.name
    if kind == "bool":
        if name == "and":
            f = lambda x, y: (min(x[0], y[0]), min(x[1], y[1]))  # noqa: E731
        elif name == "or":
            f = lambda x, y: (max(x[0], y[0]), max(x[1], y[1]))  # noqa: E731
        else:
            f = lambda x, y: (0 if x == y == (0, 0) else 0, 1)  # noqa: E731
        return [_ewise(interp.ctx, out.shape, out.dtype, ins, f,
                       uni0=a.uni0 and b.uni0)]

    def f(x, y):
        x_in = x[0] >= 0 and x[1] < (1 << (bits - 1 if kind == "int" else bits))
        y_in = y[0] >= 0 and y[1] < (1 << (bits - 1 if kind == "int" else bits))
        if name == "and":
            # x & y <= min(x, y) for any nonneg in-range operand; with one
            # wrapped operand the other nonneg bound still caps the result.
            if x_in and y_in:
                return (0, min(x[1], y[1]))
            if x_in:
                return (0, x[1])
            if y_in:
                return (0, y[1])
        elif x_in and y_in:  # or / xor
            return (0, _up2m1(max(x[1], y[1])))
        # Machine result is some in-range lane value; true == machine for
        # bitwise ops (they are residue functions), so full range is sound.
        if kind == "int":
            return (-(1 << (bits - 1)), (1 << (bits - 1)) - 1)
        return (0, (1 << bits) - 1)

    return [_ewise(interp.ctx, out.shape, out.dtype, ins, f,
                   uni0=a.uni0 and b.uni0)]


@_rule("not")
def _r_not(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    kind, bits = _dkind(out.dtype)
    if kind == "bool":
        f = lambda x: (1 - x[1], 1 - x[0])  # noqa: E731
    else:
        f = lambda x: (-x[1] - 1, -x[0] - 1)  # ~x == -x - 1 (ring)  # noqa: E731
    return [_ewise(interp.ctx, out.shape, out.dtype, ins, f, uni0=a.uni0)]


@_rule("shift_left")
def _r_shl(interp, eqn, ins, where):
    a, s = ins
    out = _out_aval(eqn)
    s = interp.ctx.observe(s, where, "shift amount")

    def f(x, sh):
        slo, shi = max(sh[0], 0), max(sh[1], 0)
        cands = (x[0] << slo, x[0] << shi, x[1] << slo, x[1] << shi)
        return (min(cands), max(cands))

    # Ring-compatible: v << s is v * 2^s mod 2^w; no observation on v.
    return [_ewise(interp.ctx, out.shape, out.dtype, ins, f,
                   uni0=a.uni0 and s.uni0)]


@_rule("shift_right_arithmetic", "shift_right_logical")
def _r_shr(interp, eqn, ins, where):
    a, s = ins
    out = _out_aval(eqn)
    # OBSERVATION: a right shift reads the lane's bit pattern as a value;
    # a wrapped operand shifts garbage. The operand must be in-range.
    a = interp.ctx.observe(a, where, "right-shift operand")
    s = interp.ctx.observe(s, where, "shift amount")
    logical = eqn.primitive.name == "shift_right_logical"
    kind, bits = _dkind(a.dtype)
    if logical and kind == "int":
        a2 = a  # logical shift on signed: require nonneg or give machine range
        neg = any(c[0] < 0 for row in a2.cells for c in row)
        if neg:
            return [full_range(out.shape, out.dtype)]

    def f(x, sh):
        slo, shi = max(sh[0], 0), max(sh[1], 0)
        cands = (x[0] >> slo, x[0] >> shi, x[1] >> slo, x[1] >> shi)
        return (min(cands), max(cands))

    return [_ewise(interp.ctx, out.shape, out.dtype, ins, f,
                   uni0=a.uni0 and s.uni0)]


def _distinct_singleton_rows(av: AbstractArray) -> bool:
    if not av.shape or av.r0 != av.shape[0] or av.r0 <= 1:
        return False
    vals = []
    for i in range(av.r0):
        los = [av.cells[i][j] for j in range(av.r1)]
        lo = min(c[0] for c in los)
        hi = max(c[1] for c in los)
        if lo != hi:
            return False
        vals.append(lo)
    return len(set(vals)) == len(vals)


@_rule("eq", "ne", "lt", "le", "gt", "ge")
def _r_cmp(interp, eqn, ins, where):
    a, b = ins
    out = _out_aval(eqn)
    # OBSERVATION: comparisons read true values (signed lanes must hold
    # their true value; unsigned/bool compare residues by definition).
    a = interp.ctx.observe(a, where, "comparison lhs")
    b = interp.ctx.observe(b, where, "comparison rhs")
    name = eqn.primitive.name
    nz0 = False
    if name == "eq":
        # One-hot detection: distinct constant rows vs an axis-0-uniform
        # value -> at most one row can match. dist0 carries the same
        # distinctness promise for tables longer than ROW_CAP.
        def distinct(v):
            return v.dist0 or _distinct_singleton_rows(v)

        if (distinct(a) and b.uni0) or (distinct(b) and a.uni0):
            nz0 = True

    def f(x, y):
        lo, hi = 0, 1
        if name == "eq":
            if x[1] < y[0] or y[1] < x[0]:
                hi = 0
            elif x[0] == x[1] == y[0] == y[1]:
                lo = 1
        elif name == "ne":
            if x[1] < y[0] or y[1] < x[0]:
                lo = 1
            elif x[0] == x[1] == y[0] == y[1]:
                hi = 0
        elif name == "lt":
            if x[1] < y[0]:
                lo = 1
            if x[0] >= y[1]:
                hi = 0
        elif name == "le":
            if x[1] <= y[0]:
                lo = 1
            if x[0] > y[1]:
                hi = 0
        elif name == "gt":
            if x[0] > y[1]:
                lo = 1
            if x[1] <= y[0]:
                hi = 0
        elif name == "ge":
            if x[0] >= y[1]:
                lo = 1
            if x[1] < y[0]:
                hi = 0
        return (lo, hi)

    return [_ewise(interp.ctx, out.shape, out.dtype, ins, f, nz0=nz0,
                   uni0=a.uni0 and b.uni0)]


@_rule("min", "max", "clamp", "rem", "div", "abs", "sign")
def _r_order(interp, eqn, ins, where):
    out = _out_aval(eqn)
    name = eqn.primitive.name
    ins = [interp.ctx.observe(o, where, f"{name} operand") for o in ins]
    if any(_dkind(o.dtype)[0] == "float" for o in ins) \
            and name in ("div", "rem"):
        interp.ctx.violate("float", where,
                           f"float {name} is never exact-integer")
        return [top(out.shape, out.dtype)]
    if name == "min":
        f = lambda x, y: (min(x[0], y[0]), min(x[1], y[1]))  # noqa: E731
    elif name == "max":
        f = lambda x, y: (max(x[0], y[0]), max(x[1], y[1]))  # noqa: E731
    elif name == "clamp":
        f = lambda lo, x, hi: (  # noqa: E731
            min(max(x[0], lo[0]), hi[1]), max(min(x[1], hi[1]), lo[0]))
    elif name == "abs":
        f = lambda x: (  # noqa: E731
            0 if x[0] <= 0 <= x[1] else min(abs(x[0]), abs(x[1])),
            max(abs(x[0]), abs(x[1])))
    elif name == "sign":
        f = lambda x: (-1 if x[0] < 0 else (0 if x[0] == 0 else 1),  # noqa: E731
                       1 if x[1] > 0 else (0 if x[1] == 0 else -1))
    elif name == "rem":
        def f(x, y):
            m = max(abs(y[0]), abs(y[1]))
            return (-m + 1 if x[0] < 0 else 0, m - 1)
    else:  # div (integer)
        def f(x, y):
            if y[0] <= 0 <= y[1]:
                return (-max(abs(x[0]), abs(x[1])), max(abs(x[0]), abs(x[1])))
            cands = []
            for xv in x:
                for yv in y:
                    q = abs(xv) // abs(yv)
                    cands.append(q if (xv >= 0) == (yv > 0) else -q)
            return (min(cands) - 1, max(cands) + 1)

    res = _ewise(interp.ctx, out.shape, out.dtype, ins, f)
    if _dkind(out.dtype)[0] == "float":
        # min/max/clamp/abs/sign SELECT (or negate) one operand value:
        # exactness is preserved whenever every float operand carries the
        # certificate, and the result magnitude is within operand hulls.
        res.exactf = _check_float_exact(interp, where, ins, res.joined())
    return [res]


@_rule("integer_pow")
def _r_ipow(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    y = eqn.params["y"]

    def f(x):
        cands = [x[0] ** y, x[1] ** y]
        if y % 2 == 0 and x[0] <= 0 <= x[1]:
            cands.append(0)
        return (min(cands), max(cands))

    return [_ewise(interp.ctx, out.shape, out.dtype, ins, f, uni0=a.uni0)]


@_rule("select_n")
def _r_select(interp, eqn, ins, where):
    out = _out_aval(eqn)
    pred, cases = ins[0], ins[1:]
    kind, _ = _dkind(out.dtype)
    if kind == "float" and not all(c.exactf for c in cases):
        interp.ctx.violate("float", where,
                           "select over non-exact float branches")
    r0 = max(c.r0 for c in cases)
    r1 = max(c.r1 for c in cases)
    plo, phi = pred.joined()
    if plo == phi and 0 <= plo < len(cases):
        chosen = [cases[plo]]
    else:
        chosen = cases
    cells = [
        [
            (min(c.cell(i, j)[0] for c in chosen),
             max(c.cell(i, j)[1] for c in chosen))
            for j in range(r1)
        ]
        for i in range(r0)
    ]
    return [mk(out.shape, out.dtype, cells,
               uni0=pred.uni0 and all(c.uni0 for c in chosen),
               exactf=all(c.exactf for c in chosen))]


@_rule("convert_element_type")
def _r_convert(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    skind, _ = _dkind(a.dtype)
    dkind, dbits = _dkind(out.dtype)
    flags = dict(nz0=a.nz0, uni0=a.uni0)
    if dkind == "float":
        # int/bool -> float: exact iff |v| <= 2^24 and the source is true.
        a2 = interp.ctx.observe(a, where, "int->float convert source")
        lo, hi = a2.joined()
        if skind == "float":
            flags["exactf"] = a.exactf
        elif max(abs(lo), abs(hi)) <= EXACT_F32:
            flags["exactf"] = True
        else:
            interp.ctx.violate(
                "float", where,
                f"convert to float of interval [{lo}, {hi}] exceeds the "
                "2^24 exact-integer float32 range",
            )
        return [mk(out.shape, out.dtype, a2.cells, **flags)]
    if skind == "float":
        if not a.exactf:
            why = f" [{a.fwhy}]" if a.fwhy else ""
            interp.ctx.violate(
                "float", where,
                "float->int convert of a non-exact float (value may have "
                f"rounded; only exact-integer floats are vetted){why}",
            )
            return [full_range(out.shape, out.dtype)]
        a = interp.ctx.observe(
            AbstractArray(a.shape, np.dtype(np.int32), a.cells, nz0=a.nz0,
                          uni0=a.uni0),
            where, "float->int convert",
        )
        return [mk(out.shape, out.dtype, a.cells, **flags)]
    if dkind == "int":
        # Converting into a signed lane observes the true value unless the
        # source residue provably fits (mk reduces unsigned for us).
        if skind == "int":
            a = interp.ctx.observe(a, where, "int->int convert")
        return [mk(out.shape, out.dtype, a.cells, **flags)]
    # -> uint / bool: residue (mk normalizes), always defined.
    if dkind == "bool":
        cells = [[(0 if c == (0, 0) else (1 if c[0] > 0 or c[1] < 0 else 0),
                   0 if c == (0, 0) else 1)] for row in a.cells
                 for c in [row[0]]]
        # simpler: nonzero test per joined cells
        lo, hi = a.joined()
        nz_lo = 1 if (lo > 0 or hi < 0) else 0
        nz_hi = 0 if (lo == 0 and hi == 0) else 1
        return [mk(out.shape, out.dtype, [[(nz_lo, nz_hi)]], **flags)]
    return [mk(out.shape, out.dtype, a.cells, **flags)]


@_rule("device_put", "copy", "stop_gradient")
def _r_identity(interp, eqn, ins, where):
    return [ins[0]]


@_rule("broadcast_in_dim")
def _r_broadcast(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    bdims = eqn.params["broadcast_dimensions"]
    # Which operand axis feeds result axes 0/1 (None: fresh broadcast dim)?
    src = {r: o for o, r in enumerate(bdims)}

    def src_axis(res_ax):
        o = src.get(res_ax)
        if o is None:
            return None, True  # fresh dim: uniform along it
        if a.shape[o] == 1 and len(out.shape) > res_ax and out.shape[res_ax] != 1:
            return None, True  # broadcast from size-1: uniform
        return o, False

    s0, fresh0 = src_axis(0)
    s1, _ = src_axis(1)
    uni0 = a.uni0 if s0 == 0 else (True if fresh0 else False)
    if s0 is not None and s0 not in (0, 1):
        s0 = None
    if s1 is not None and s1 not in (0, 1):
        s1 = None
    nz0 = a.nz0 and s0 == 0
    res = take_axes(a, out.shape, s0, s1, nz0=nz0)
    res.uni0 = uni0 or res.uni0
    res.exactf = a.exactf
    # Broadcasting only replicates: constant-distinct rows stay so as
    # long as result axis 0 is operand axis 0 unchanged.
    if a.dist0 and s0 == 0 and out.shape[0] == a.shape[0]:
        res.dist0 = True
    return [res]


@_rule("reshape")
def _r_reshape(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    old, new = a.shape, out.shape
    flags = dict(exactf=a.exactf)
    if old and new and old[0] == new[0]:
        keep_r1 = len(old) > 1 and len(new) > 1 and old[1] == new[1]
        res = take_axes(a, new, 0, 1 if keep_r1 else None,
                        nz0=a.nz0, **flags)
        res.uni0 = a.uni0
        return [res]
    rows = a.rows0() if old and a.r0 > 1 else None
    if rows is not None and new and new[0] % old[0] == 0 and old[0] > 1:
        # leading-axis split of each old row into k new rows (C order)
        k = new[0] // old[0]
        if k * old[0] == new[0] and len(old) >= 2 and old[1] % k == 0:
            pass  # fallthrough to repeat expansion below
        rep = [r for r in rows for _ in range(k)]
        if new[0] <= ROW_CAP:
            return [mk(new, out.dtype, [[c] for c in rep], **flags)]
    if rows is not None and new and old[0] % max(new[0], 1) == 0 and new[0] >= 1:
        # leading-axis merge: groups of consecutive old rows join
        g = old[0] // new[0]
        grouped = []
        for i in range(new[0]):
            chunk = rows[i * g:(i + 1) * g]
            grouped.append((min(c[0] for c in chunk),
                            max(c[1] for c in chunk)))
        if new[0] <= ROW_CAP:
            return [mk(new, out.dtype, [[c] for c in grouped], **flags)]
    return [mk(new, out.dtype, [[a.joined()]], **flags)]


@_rule("squeeze")
def _r_squeeze(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    dims = set(eqn.params["dimensions"])
    remaining = [i for i in range(len(a.shape)) if i not in dims]
    s0 = remaining[0] if len(remaining) >= 1 else None
    s1 = remaining[1] if len(remaining) >= 2 else None
    s0 = s0 if s0 in (0, 1) else None
    s1 = s1 if s1 in (0, 1) else None
    res = take_axes(a, out.shape, s0, s1, nz0=a.nz0 and s0 == 0)
    res.uni0 = a.uni0 if s0 == 0 else res.uni0
    return [res]


@_rule("transpose")
def _r_transpose(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    perm = eqn.params["permutation"]
    s0 = perm[0] if len(perm) >= 1 and perm[0] in (0, 1) else None
    s1 = perm[1] if len(perm) >= 2 and perm[1] in (0, 1) else None
    res = take_axes(a, out.shape, s0, s1, nz0=a.nz0 and s0 == 0)
    res.uni0 = a.uni0 if s0 == 0 else res.uni0
    return [res]


@_rule("slice")
def _r_slice(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    starts = eqn.params["start_indices"]
    strides = eqn.params.get("strides") or (1,) * len(starts)

    def rows_for(ax, get):
        n_out = out.shape[ax]
        return [get(starts[ax] + i * strides[ax]) for i in range(n_out)]

    cells = None
    if a.r0 > 1 and out.shape and out.shape[0] <= ROW_CAP:
        rows_idx = [starts[0] + i * strides[0] for i in range(out.shape[0])]
        if a.r1 > 1 and len(out.shape) > 1 and out.shape[1] <= ROW_CAP:
            cols_idx = [starts[1] + j * strides[1]
                        for j in range(out.shape[1])]
            cells = [[a.cells[i][j] for j in cols_idx] for i in rows_idx]
        else:
            cells = [
                [(min(c[0] for c in a.cells[i]),
                  max(c[1] for c in a.cells[i]))]
                for i in rows_idx
            ]
    elif a.r1 > 1 and len(out.shape) > 1 and out.shape[1] <= ROW_CAP and (
        not a.shape or a.shape[0] == out.shape[0] or a.r0 == 1
    ):
        cols_idx = [starts[1] + j * strides[1] for j in range(out.shape[1])]
        cells = [[a.cells[0][j] for j in cols_idx]]
    if cells is None:
        cells = [[a.joined()]]
    return [mk(out.shape, out.dtype, cells, nz0=False, uni0=a.uni0,
               exactf=a.exactf)]


@_rule("concatenate")
def _r_concat(interp, eqn, ins, where):
    out = _out_aval(eqn)
    dim = eqn.params["dimension"]
    if dim == 0 and out.shape[0] <= ROW_CAP:
        r1 = max(o.r1 for o in ins)
        cells = []
        for o in ins:
            n = o.shape[0]
            for i in range(n):
                cells.append([o.cell(i, j) for j in range(r1)])
        return [mk(out.shape, out.dtype, cells,
                   exactf=all(o.exactf for o in ins))]
    if dim == 1 and len(out.shape) > 1 and out.shape[1] <= ROW_CAP:
        r0 = max(o.r0 for o in ins)
        cells = [[] for _ in range(r0)]
        for o in ins:
            for j in range(o.shape[1]):
                for i in range(r0):
                    cells[i].append(o.cell(i, j))
        return [mk(out.shape, out.dtype, cells,
                   exactf=all(o.exactf for o in ins))]
    # concat along an untracked axis: rowwise join across operands
    r0 = max(o.r0 for o in ins)
    r1 = max(o.r1 for o in ins)
    cells = [
        [
            (min(o.cell(i, j)[0] for o in ins),
             max(o.cell(i, j)[1] for o in ins))
            for j in range(r1)
        ]
        for i in range(r0)
    ]
    return [mk(out.shape, out.dtype, cells,
               nz0=all(o.nz0 for o in ins),
               uni0=all(o.uni0 for o in ins),
               exactf=all(o.exactf for o in ins))]


@_rule("pad")
def _r_pad(interp, eqn, ins, where):
    a, pv = ins
    out = _out_aval(eqn)
    cfg = eqn.params["padding_config"]
    pcell = pv.joined()

    def pad_axis(rows, n_in, n_out, lo, hi, interior):
        res = []
        for i in range(n_out):
            src = i - lo
            if src < 0 or src > (n_in - 1) * (interior + 1):
                res.append(pcell)
            elif src % (interior + 1) == 0:
                res.append(rows[src // (interior + 1)])
            else:
                res.append(pcell)
        return res

    # Padding on axes >= 2 is untracked by the (r0, r1) grid: fold the pad
    # value into every kept cell so those positions stay covered.
    deep_pad = any(c != (0, 0, 0) for c in cfg[2:])

    def keep(c):
        return _hull(c, pcell) if deep_pad else c

    if (a.shape and out.shape and out.shape[0] <= ROW_CAP
            and a.shape[0] <= 4 * ROW_CAP):
        lo, hi, interior = cfg[0]
        if (len(out.shape) > 1 and 1 <= out.shape[1] <= ROW_CAP
                and a.shape[1] <= ROW_CAP):
            # Full per-cell grid on both tracked axes. Crucially this runs
            # even when a.r1 == 1 (e.g. a (20, 1) -> (20, 2) column pad in
            # an associative-scan interleave): the padded column must read
            # as the pad value, not the data hull, or the even/odd merge
            # add doubles every bound downstream.
            lo1, hi1, int1 = cfg[1]
            grid = [
                pad_axis([keep(a.cell(i, j)) for j in range(a.shape[1])],
                         a.shape[1], out.shape[1], lo1, hi1, int1)
                for i in range(a.shape[0])
            ]
            prow = [pcell] * out.shape[1]
            cells = pad_axis(grid, a.shape[0], out.shape[0], lo, hi, interior)
            cells = [(r if isinstance(r, list) else prow) for r in cells]
            return [mk(out.shape, out.dtype, cells, exactf=a.exactf)]
        arows = a.rows0()
        if len(out.shape) <= 1 or all(c == (0, 0, 0) for c in cfg[1:]):
            rows = [keep(c) for c in arows]
            cells = [[c] for c in pad_axis(rows, a.shape[0], out.shape[0],
                                           lo, hi, interior)]
            return [mk(out.shape, out.dtype, cells, exactf=a.exactf)]
        # Axis-1 padding on an untracked-width row: hull with the pad value.
        rows = [_hull(keep(c), pcell) for c in arows]
        cells = [[c] for c in pad_axis(rows, a.shape[0], out.shape[0],
                                       lo, hi, interior)]
        return [mk(out.shape, out.dtype, cells, exactf=a.exactf)]
    return [mk(out.shape, out.dtype, [[_hull(a.joined(), pcell)]],
               exactf=a.exactf)]


@_rule("iota")
def _r_iota(interp, eqn, ins, where):
    out = _out_aval(eqn)
    dim = eqn.params["dimension"]
    n = out.shape[dim]
    # An iota varies only along `dim`: every other axis is uniform, in
    # particular axis 0 whenever dim != 0. A float iota is exact iff its
    # largest value fits the f32 exact-integer window.
    exf = _dkind(out.dtype)[0] == "float" and max(n - 1, 0) <= EXACT_F32
    uni = dim != 0
    if dim == 0 and n <= ROW_CAP:
        return [mk(out.shape, out.dtype, [[(i, i)] for i in range(n)],
                   dist0=n > 1, exactf=exf)]
    if dim == 1 and len(out.shape) > 1 and n <= ROW_CAP:
        return [mk(out.shape, out.dtype, [[(i, i) for i in range(n)]],
                   uni0=uni, exactf=exf)]
    return [mk(out.shape, out.dtype, [[(0, max(n - 1, 0))]],
               dist0=dim == 0 and n > 1, uni0=uni, exactf=exf)]


@_rule("reduce_sum", "reduce_max", "reduce_min", "reduce_and", "reduce_or")
def _r_reduce(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    axes = set(eqn.params["axes"])
    name = eqn.primitive.name
    if name in ("reduce_max", "reduce_min"):
        a = interp.ctx.observe(a, where, f"{name} operand")

    # Multiplicity of untracked reduced elements per surviving cell.
    mult = 1
    for ax in axes:
        if ax == 0 and a.r0 > 1:
            continue
        if ax == 1 and a.r1 > 1:
            continue
        mult *= a.shape[ax]

    red0 = 0 in axes and a.r0 > 1
    red1 = 1 in axes and a.r1 > 1

    def combine(cells_seq):
        if name == "reduce_sum":
            lo = sum(c[0] for c in cells_seq)
            hi = sum(c[1] for c in cells_seq)
        elif name == "reduce_max":
            lo = max(c[0] for c in cells_seq)
            hi = max(c[1] for c in cells_seq)
        elif name == "reduce_min":
            lo = min(c[0] for c in cells_seq)
            hi = min(c[1] for c in cells_seq)
        elif name == "reduce_and":
            lo = min(c[0] for c in cells_seq)
            hi = min(c[1] for c in cells_seq)
        else:  # reduce_or
            lo = max(c[0] for c in cells_seq)
            hi = max(c[1] for c in cells_seq)
        return (lo, hi)

    def apply_mult(c):
        if mult == 1 or name != "reduce_sum":
            return c
        return (c[0] * mult, c[1] * mult)

    if (a.nz0 and name == "reduce_sum" and 0 in axes
            and (1 not in axes or a.r1 == 1)):
        # Masked-select: at most one element nonzero along axis 0, so the
        # sum is one of the rows (or 0) — join, don't sum. This is what
        # keeps one-hot table selects at per-limb precision. Applies even
        # when the row grid is collapsed (r0 == 1: `mk` folds uniform
        # rows, e.g. a W2-bounded table read through a Pallas Ref) — the
        # single tracked cell covers every row, so the join is that cell
        # extended with 0; only the OTHER reduced axes still multiply.
        mult_no0 = 1
        for ax in axes:
            if ax == 0:
                continue
            mult_no0 *= a.shape[ax]
        red0_cells = [
            (min(0, min(a.cells[i][j][0] for i in range(a.r0))),
             max(0, max(a.cells[i][j][1] for i in range(a.r0))))
            for j in range(a.r1)
        ]
        new_cells = [[(c[0] * mult_no0, c[1] * mult_no0)]
                     for c in red0_cells]
        res = mk(out.shape, out.dtype, new_cells, exactf=a.exactf)
        if _dkind(out.dtype)[0] == "float":
            # At most one nonzero along axis 0, so the accumulated
            # |partial sum| over the remaining mult_no0 untracked terms
            # is exactly the derived cell bound — the hull IS the sound
            # sum bound here.
            res.exactf = _check_float_exact(interp, where, ins,
                                            res.joined())
        return [res]

    cells = a.cells
    if red0:
        cells = [[combine([cells[i][j] for i in range(len(cells))])
                  for j in range(len(cells[0]))]]
    if red1:
        cells = [[combine(row)] for row in cells]
    # remap: surviving tracked axes shift into result axes 0/1
    if red0 and not red1:
        new_cells = [[apply_mult(c)] for c in cells[0]]  # old axis1 -> axis0
    elif red1 and not red0:
        new_cells = [[apply_mult(row[0])] for row in cells]
    elif red0 and red1:
        new_cells = [[apply_mult(cells[0][0])]]
    else:
        new_cells = [[apply_mult(c) for c in row] for row in cells]
    res = mk(out.shape, out.dtype, new_cells, exactf=False)
    if _dkind(out.dtype)[0] == "float":
        if name == "reduce_sum":
            # SOUND rule: every partial sum of the reduction, under ANY
            # association order, is bounded by the ACCUMULATED sum of
            # per-element magnitude bounds — the result hull is not
            # enough (signs may cancel in the true sum while a partial
            # sum leaves the 2^24 window and rounds).
            def cabs(c):
                return max(abs(c[0]), abs(c[1]))

            if red0 and red1:
                accs = [mult * sum(cabs(a.cells[i][j])
                                   for i in range(a.r0)
                                   for j in range(a.r1))]
            elif red0:
                accs = [mult * sum(cabs(a.cells[i][j])
                                   for i in range(a.r0))
                        for j in range(a.r1)]
            elif red1:
                accs = [mult * sum(cabs(c) for c in row)
                        for row in a.cells]
            else:
                accs = [mult * cabs(c) for row in a.cells for c in row]
            acc_max = max(accs) if accs else 0
            k_terms = 1
            for ax in axes:
                k_terms *= a.shape[ax]
            interp.ctx.eqn_facts["sum_abs_bound"] = _sat(acc_max)
            interp.ctx.eqn_facts["k_terms"] = k_terms
            res.exactf = _check_float_exact(interp, where, ins,
                                            (-acc_max, acc_max))
        elif name in ("reduce_max", "reduce_min"):
            # Selection: the result is one of the operand elements.
            res.exactf = a.exactf
    return [res]


@_rule("gather")
def _r_gather(interp, eqn, ins, where):
    a, idx = ins
    out = _out_aval(eqn)
    idx = interp.ctx.observe(idx, where, "gather indices")
    return [mk(out.shape, out.dtype, [[a.joined()]], exactf=a.exactf)]


@_rule("dynamic_slice")
def _r_dynamic_slice(interp, eqn, ins, where):
    a = ins[0]
    out = _out_aval(eqn)
    for s in ins[1:]:
        interp.ctx.observe(s, where, "dynamic_slice start")
    # Unknown offset: join along sliced tracked axes; a tracked axis whose
    # full extent survives keeps its rows.
    keep0 = a.shape and out.shape and a.shape[0] == out.shape[0]
    keep1 = (len(a.shape) > 1 and len(out.shape) > 1
             and a.shape[1] == out.shape[1])
    res = take_axes(a, out.shape, 0 if keep0 else None, 1 if keep1 else None)
    res.exactf = a.exactf
    return [res]


@_rule("dynamic_update_slice")
def _r_dus(interp, eqn, ins, where):
    a, upd = ins[0], ins[1]
    for s in ins[2:]:
        interp.ctx.observe(s, where, "dynamic_update_slice start")
    out = _out_aval(eqn)
    u = upd.joined()
    cells = [[_hull(c, u) for c in row] for row in a.cells]
    return [mk(out.shape, out.dtype, cells, exactf=a.exactf and upd.exactf)]


@_rule("scatter")
def _r_scatter(interp, eqn, ins, where):
    a, _idx, upd = ins[0], ins[1], ins[2]
    out = _out_aval(eqn)
    u = upd.joined()
    cells = [[_hull(c, u) for c in row] for row in a.cells]
    return [mk(out.shape, out.dtype, cells, exactf=a.exactf and upd.exactf)]


@_rule("rev")
def _r_rev(interp, eqn, ins, where):
    (a,) = ins
    out = _out_aval(eqn)
    dims = set(eqn.params["dimensions"])
    cells = a.cells
    if 0 in dims and a.r0 > 1:
        cells = cells[::-1]
    if 1 in dims and a.r1 > 1:
        cells = [row[::-1] for row in cells]
    return [mk(out.shape, out.dtype, cells, uni0=a.uni0, exactf=a.exactf)]


@_rule("dot_general")
def _r_dot(interp, eqn, ins, where):
    a, b = ins
    out = _out_aval(eqn)
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    kind, _ = _dkind(out.dtype)
    K = 1
    for d in lc:
        K *= a.shape[d]
    # One-hot contraction: if either operand is nz0 along its (single)
    # contracted axis 0, at most one term of the sum is nonzero — the
    # result is one product, not K of them. This is what makes the f32
    # MXU table select (one-hot (255,B) against a (255,20) window table)
    # provably exact instead of 255x over-approximated.
    if (a.nz0 and tuple(lc) == (0,)) or (b.nz0 and tuple(rc) == (0,)):
        K = 1
    ah = a.joined()
    bh = b.joined()
    ps = (ah[0] * bh[0], ah[0] * bh[1], ah[1] * bh[0], ah[1] * bh[1])
    plo, phi = min(ps), max(ps)
    # Partial sums are bounded by K * max|product| regardless of order:
    # this is the ACCUMULATED sum bound Sum|products|, not the
    # per-element bound — the quantity that must stay <= 2^24 for the
    # f32 contraction to be bit-exact at Precision.HIGHEST.
    bound = K * max(abs(plo), abs(phi))
    exactf = False
    if kind == "float":
        interp.ctx.eqn_facts["sum_abs_bound"] = _sat(bound)
        interp.ctx.eqn_facts["k_terms"] = K
        ok = _check_float_exact(interp, where, ins, (-bound, bound))
        prec = eqn.params.get("precision")
        prec_ok = False
        if prec is not None:
            try:
                from jax import lax as _lax
                ps_ = prec if isinstance(prec, (tuple, list)) else (prec,)
                prec_ok = all(p == _lax.Precision.HIGHEST for p in ps_)
            except Exception:
                prec_ok = False
        if not prec_ok:
            interp.ctx.violate(
                "float", where,
                "float dot_general without Precision.HIGHEST: the TPU MXU "
                "lowers default-precision f32 dots through bfloat16 passes "
                "that truncate 13-bit limbs",
            )
            ok = False
        exactf = ok
    # Result axis 0 <- first lhs batch dim, else first free lhs dim.
    free_l = [d for d in range(len(a.shape)) if d not in lc and d not in lb]
    res_ax0_src = (lb[0] if lb else (free_l[0] if free_l else None))
    s0 = res_ax0_src if res_ax0_src in (0, 1) else None
    base = take_axes(a, out.shape, s0, None)
    cells = [
        [(K * min(c[0] * bh[0], c[0] * bh[1], c[1] * bh[0], c[1] * bh[1]),
          K * max(c[0] * bh[0], c[0] * bh[1], c[1] * bh[0], c[1] * bh[1]))
         for c in row]
        for row in base.cells
    ]
    return [mk(out.shape, out.dtype, cells, exactf=exactf)]



# ---------------------------------------------------------------------------
# Control flow.

def _scan_elem(x: AbstractArray) -> AbstractArray:
    """Abstract one scanned-over element of an xs input (strip the leading
    scan axis: element axis 0 <- xs axis 1, everything else joined)."""
    elem_shape = x.shape[1:]
    return take_axes(x, elem_shape, 1 if len(x.shape) > 1 else None, None,
                     exactf=x.exactf)


def _stack_ys(y: AbstractArray, length: int) -> AbstractArray:
    """Abstract the stacked ys output (new leading scan axis; body-output
    axis 0 moves to axis 1). The body value is a fixpoint over-approximation
    of every iteration, so broadcasting it along the scan axis is sound."""
    out_shape = (length,) + y.shape
    res = take_axes(y, out_shape, None, 0, exactf=y.exactf)
    res.uni0 = True
    return res


def _fixpoint(interp, closed, n_consts, consts_and_carry_init, extra_args,
              where, narrow=None, min_trips=0):
    """Run `closed`'s body to a carry fixpoint with staged widening.

    consts_and_carry_init: (const_avals, carry_avals); extra_args are the
    per-iteration xs elements (already element-shaped, loop-invariant
    abstractions). Returns the final (carry_out, other_outs) of a last
    *unmuted* pass evaluated at the fixpoint carry. With min_trips >= 1
    (statically known to iterate), the carry-out is the body output alone
    — the loop exit value is the LAST iteration's output, so the init
    need not be joined in (it matters for weak-rep inits the body
    immediately settles, e.g. the 2*W2 sum feeding fe_batch_inv's
    Fermat scan).
    """
    const_in, carry0 = consts_and_carry_init
    carry = list(carry0)
    interp.ctx.in_loop += 1
    interp.ctx.mute += 1
    try:
        for it in range(_MAX_FIX_ITERS):
            args = list(const_in) + list(carry) + list(extra_args)
            outs = interp.eval_closed(closed, args, where)
            new_carry = outs[: len(carry)]
            nxt = []
            stable = True
            for old, new in zip(carry, new_carry, strict=True):
                r0 = max(old.r0, new.r0)
                r1 = max(old.r1, new.r1)
                cells = []
                for i in range(r0):
                    rowc = []
                    for j in range(r1):
                        oc, nc = old.cell(i, j), new.cell(i, j)
                        h = _hull(oc, nc)
                        if h != oc and it >= 3:
                            h = _widen_cell(oc, h)
                        rowc.append(h)
                    cells.append(rowc)
                merged = AbstractArray(
                    old.shape, old.dtype, _collapse_if_uniform(cells),
                    nz0=old.nz0 and new.nz0, uni0=old.uni0 and new.uni0,
                    exactf=old.exactf and new.exactf,
                )
                if narrow is not None:
                    merged = narrow(len(nxt), merged)
                # Stability must be judged on the *narrowed* carry: a pinned
                # counter whose raw hull grows each pass (0,31)->(0,32) but
                # clamps back would otherwise never read as stable.
                if (merged.nz0, merged.uni0, merged.exactf) != (
                        old.nz0, old.uni0, old.exactf):
                    stable = False
                else:
                    for i in range(r0):
                        for j in range(r1):
                            if merged.cell(i, j) != old.cell(i, j):
                                stable = False
                                break
                        if not stable:
                            break
                nxt.append(merged)
            carry = nxt
            if stable:
                break
        else:
            carry = [top(c.shape, c.dtype) for c in carry]
        # Decreasing (narrowing) passes: staged widening can overshoot the
        # least fixpoint (e.g. jump a limb bound from 8191 past W2=15631 to
        # 16383, where mul chains stop being int32-safe). Re-evaluate the
        # body at the widened carry and shrink each cell to
        # hull(init, body_out) ∩ current. The final unmuted pass below
        # re-checks the body at the narrowed carry, so an unsound shrink
        # cannot escape silently.
        for _ in range(4):
            args = list(const_in) + list(carry) + list(extra_args)
            outs = interp.eval_closed(closed, args, where)
            shrunk = False
            nxt = []
            for idx, (init0, old, new) in enumerate(
                    zip(carry0, carry, outs[: len(carry)], strict=True)):
                r0 = max(old.r0, new.r0, init0.r0)
                r1 = max(old.r1, new.r1, init0.r1)
                cells = []
                for i in range(r0):
                    rowc = []
                    for j in range(r1):
                        oc = old.cell(i, j)
                        ic, nc = init0.cell(i, j), new.cell(i, j)
                        cand = (min(ic[0], nc[0]), max(ic[1], nc[1]))
                        h = (max(oc[0], cand[0]), min(oc[1], cand[1]))
                        if h[0] > h[1]:
                            h = oc
                        if h != oc:
                            shrunk = True
                        rowc.append(h)
                    cells.append(rowc)
                merged = AbstractArray(
                    old.shape, old.dtype, _collapse_if_uniform(cells),
                    nz0=old.nz0, uni0=old.uni0, exactf=old.exactf,
                )
                if narrow is not None:
                    merged = narrow(idx, merged)
                nxt.append(merged)
            carry = nxt
            if not shrunk:
                break
    finally:
        interp.ctx.mute -= 1
    try:
        args = list(const_in) + list(carry) + list(extra_args)
        outs = interp.eval_closed(closed, args, where)
    finally:
        interp.ctx.in_loop -= 1
    final_carry = []
    for old, new in zip(carry, outs[: len(carry)], strict=True):
        if min_trips >= 1:
            final_carry.append(new)
        else:
            final_carry.append(join_values(old, new)
                               if old.shape == new.shape else new)
    return final_carry, outs[len(carry):]


def _counter_carries(jaxpr, n_consts: int, n_carry: int):
    """Find carries that are pure counters: body output k is exactly
    `add(carry_k, literal)`. Their range over the whole loop is known
    statically from the trip count — pinning them keeps indexing and
    trip-count arithmetic (`w = N-1-i`, `db1[w]`) finitely bounded
    instead of widening to infinity."""
    out = {}
    Lit = jax_core.Literal
    for k in range(n_carry):
        ov = jaxpr.outvars[k]
        iv = jaxpr.invars[n_consts + k]
        for e in jaxpr.eqns:
            if e.outvars and e.outvars[0] is ov:
                if e.primitive.name == "add":
                    a, b = e.invars
                    if a is iv and isinstance(b, Lit):
                        out[k] = int(b.val)
                    elif b is iv and isinstance(a, Lit):
                        out[k] = int(a.val)
                break
    return out


@_rule("scan")
def _r_scan(interp, eqn, ins, where):
    p = eqn.params
    n_consts, n_carry = p["num_consts"], p["num_carry"]
    length = p["length"]
    closed = p["jaxpr"]
    consts = ins[:n_consts]
    carry0 = ins[n_consts:n_consts + n_carry]
    xs = ins[n_consts + n_carry:]
    elems = [_scan_elem(x) for x in xs]

    counters = _counter_carries(closed.jaxpr, n_consts, n_carry)
    pins = {}
    for k, step in counters.items():
        lo0, hi0 = carry0[k].joined()
        if abs(lo0) < INF and abs(hi0) < INF and length:
            span = step * (length - 1)
            pins[k] = (lo0 + min(span, 0), hi0 + max(span, 0))

    def narrow(k, av):
        pin = pins.get(k)
        if pin is None:
            return av
        cells = [[(max(lo, pin[0]), min(hi, pin[1])) for lo, hi in row]
                 for row in av.cells]
        return AbstractArray(av.shape, av.dtype, cells, nz0=av.nz0,
                             uni0=av.uni0, exactf=av.exactf)

    carry_out, y_body = _fixpoint(
        interp, closed, n_consts, (consts, carry0), elems, where,
        narrow=narrow, min_trips=1 if (length or 0) >= 1 else 0)
    ys = [_stack_ys(y, length) for y in y_body]
    return list(carry_out) + ys


def _fori_shaped(cond_closed):
    """Detect the fori_loop cond pattern: a single `lt` of one carry
    element against a literal/const. Returns (carry_index, bound) or
    None. Anything else is a data-dependent trip count."""
    jaxpr = cond_closed.jaxpr
    if len(jaxpr.eqns) != 1:
        return None
    eqn = jaxpr.eqns[0]
    if eqn.primitive.name != "lt" or len(jaxpr.outvars) != 1:
        return None
    if eqn.outvars[0] is not jaxpr.outvars[0]:
        return None
    lhs, rhs = eqn.invars
    Lit = jax_core.Literal
    if isinstance(lhs, Lit) or not isinstance(rhs, Lit):
        return None
    try:
        idx = list(jaxpr.invars).index(lhs)
    except ValueError:
        return None
    return idx, int(rhs.val)


@_rule("while")
def _r_while(interp, eqn, ins, where):
    p = eqn.params
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    cond_closed, body_closed = p["cond_jaxpr"], p["body_jaxpr"]
    cond_consts = ins[:cn]
    body_consts = ins[cn:cn + bn]
    carry0 = ins[cn + bn:]

    fori = _fori_shaped(cond_closed)
    narrow = None
    if fori is None:
        interp.ctx.violate(
            "loop", where,
            "data-dependent while_loop trip count: cond jaxpr is not the "
            "fori_loop pattern (single `lt counter const`); on TPU this "
            "re-dispatches per iteration and its timing/trip count depends "
            "on lane values — consensus kernels must use fori_loop or scan",
        )
    else:
        idx, bound = fori

        def narrow(i, av, _idx=idx - cn, _bound=bound):
            if i != _idx:
                return av
            cells = [[(min(lo, _bound), min(hi, _bound))
                      for lo, hi in row] for row in av.cells]
            return AbstractArray(av.shape, av.dtype, cells, nz0=av.nz0,
                                 uni0=av.uni0, exactf=av.exactf)

    carry_out, _ = _fixpoint(
        interp, body_closed, bn, (body_consts, carry0), [], where,
        narrow=narrow)
    # Evaluate the cond once (observation discipline on its operands).
    interp.ctx.mute += 1
    try:
        interp.eval_closed(cond_closed, list(cond_consts) + list(carry_out),
                           where + "/cond")
    finally:
        interp.ctx.mute -= 1
    return list(carry_out)


@_rule("cond")
def _r_cond(interp, eqn, ins, where):
    branches = eqn.params["branches"]
    pred, args = ins[0], ins[1:]
    interp.ctx.observe(pred, where, "cond predicate")
    outs = None
    plo, phi = pred.joined()
    idxs = range(len(branches))
    if plo == phi and 0 <= plo < len(branches):
        idxs = [plo]
    # With an unresolved predicate every branch is evaluated abstractly
    # but only one runs concretely — ref writes inside must stay weak.
    multi = len(list(idxs)) > 1
    if multi:
        interp.ctx.in_loop += 1
    try:
        for bi in idxs:
            bouts = interp.eval_closed(branches[bi], list(args),
                                       f"{where}/branch{bi}")
            if outs is None:
                outs = list(bouts)
            else:
                outs = [join_values(a, b) if a.shape == b.shape else b
                        for a, b in zip(outs, bouts, strict=True)]
    finally:
        if multi:
            interp.ctx.in_loop -= 1
    return outs


@_rule("jit", "closed_call", "core_call", "remat", "checkpoint")
def _r_call(interp, eqn, ins, where):
    closed = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    name = eqn.params.get("name", eqn.primitive.name)
    return interp.eval_closed(closed, list(ins), f"{where}/{name}")


@_rule("custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr")
def _r_custom(interp, eqn, ins, where):
    closed = eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
    return interp.eval_closed(closed, list(ins), where)


ALLOWED_PRIMITIVES = frozenset(RULES)

# Primitives whose transfer rules implement the exact-float policy: they
# either preserve the exactness certificate soundly (structural moves,
# selections, the checked add/mul/dot/reduce arithmetic) or decide the
# float question themselves (div/rem always violate). Any float32 value
# produced by a primitive OUTSIDE this set is demoted to inexact by the
# interpreter post-pass with a sourced diagnostic — an unvetted op can
# round, so the certificate cannot survive it. A deliberately mutable
# set (unlike ALLOWED_PRIMITIVES): analysis/pallas_check.py extends it
# with the Ref primitives whose rules thread exactf through VMEM.
FLOAT_VETTED = {
    # checked arithmetic (each rule proves bound <= 2^24 or violates)
    "add", "sub", "mul", "neg", "dot_general",
    "reduce_sum", "reduce_max", "reduce_min",
    # selections / comparisons (result is one of the operand values)
    "min", "max", "clamp", "abs", "sign", "select_n",
    # rules that always violate on float themselves
    "div", "rem",
    # converts (rule checks the 2^24 window / certificate)
    "convert_element_type",
    # structural moves: values are copied, never recomputed
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "slice",
    "concatenate", "pad", "rev", "gather", "dynamic_slice",
    "dynamic_update_slice", "scatter", "iota",
    "device_put", "copy", "stop_gradient",
    # control flow: certificates propagate through the recursive walk
    "scan", "while", "cond", "jit", "closed_call", "core_call",
    "remat", "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr",
}


# ---------------------------------------------------------------------------
# The interpreter.

_BANNED_64 = ("int64", "uint64", "float64")


class _Interp:
    def __init__(self, ctx: _Ctx):
        self.ctx = ctx

    def _read(self, env, v):
        if isinstance(v, jax_core.Literal):
            return from_concrete(np.asarray(v.val, dtype=v.aval.dtype))
        return env[v]

    def eval_closed(self, closed, args: List[AbstractArray],
                    where: str) -> List[AbstractArray]:
        jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        consts = closed.consts if hasattr(closed, "consts") else []
        env: Dict = {}
        for var, c in zip(jaxpr.constvars, consts, strict=True):
            env[var] = from_concrete(np.asarray(c))
        if len(args) != len(jaxpr.invars):
            raise ValueError(
                f"{where}: arity mismatch ({len(args)} args for "
                f"{len(jaxpr.invars)} invars)")
        for var, a in zip(jaxpr.invars, args, strict=True):
            env[var] = a
        for k, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            ew = f"{where}#{k}:{name}"
            if not self.ctx.mute:
                self.ctx.report.n_eqns += 1
                self.ctx.report.prim_counts[name] = (
                    self.ctx.report.prim_counts.get(name, 0) + 1)
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and str(aval.dtype) in _BANNED_64:
                    self.ctx.violate(
                        "dtype64", ew,
                        f"64-bit dtype {aval.dtype} in consensus kernel "
                        "(TPU lowers 64-bit integer ops as pairs; banned)",
                    )
            ins = [self._read(env, v) for v in eqn.invars]
            self.ctx.eqn_facts = {}
            rule = RULES.get(name)
            if rule is None:
                self.ctx.violate(
                    "allowlist", ew,
                    f"primitive `{name}` is not on the integer-deterministic "
                    "allowlist (no vetted transfer rule); add a rule to "
                    "analysis/interval.py RULES after review",
                )
                outs = [top(v.aval.shape, v.aval.dtype)
                        for v in eqn.outvars]
            else:
                try:
                    outs = rule(self, eqn, ins, ew)
                except Exception as e:  # analyzer bug, never silently pass
                    self.ctx.violate(
                        "internal", ew,
                        f"transfer rule for `{name}` raised "
                        f"{type(e).__name__}: {e}",
                    )
                    outs = [top(v.aval.shape, v.aval.dtype)
                            for v in eqn.outvars]
            _poly_transfer(eqn, ins, outs)
            _cong_transfer(eqn, ins, outs)
            self._float_post(name, ew, ins, outs)
            for var, o in zip(eqn.outvars, outs, strict=True):
                if type(var).__name__ != "DropVar":
                    env[var] = o
        return [self._read(env, v) for v in jaxpr.outvars]

    def _float_post(self, name, ew, ins, outs):
        """Exact-float post-pass, run on EVERY equation: demote float
        outputs of primitives without a vetted exact-float transfer
        (they may round), attach demotion provenance, and append the
        per-value entry to the exactness trace."""
        ctx = self.ctx
        facts = ctx.eqn_facts
        for oi, o in enumerate(outs):
            if _dkind(o.dtype)[0] != "float":
                continue
            if name not in FLOAT_VETTED:
                o.exactf = False
                o.fwhy = (f"certificate demoted at {ew}: `{name}` has no "
                          "vetted exact-float transfer")
                ctx.violate(
                    "float", ew,
                    f"float32 value produced by `{name}`, which is not on "
                    "the exact-float vetted list: the value may have "
                    "rounded, so the exactness certificate is demoted "
                    "to inexact here",
                )
            if o.exactf:
                o.fwhy = None
            elif o.fwhy is None:
                # Inherit the demotion source from the first inexact
                # float operand; otherwise this equation is the source.
                o.fwhy = next(
                    (i.fwhy for i in ins
                     if _dkind(i.dtype)[0] == "float" and i.fwhy),
                    f"certificate demoted at {ew}")
            if not ctx.mute:
                lo, hi = o.joined()
                m = max(abs(lo), abs(hi))
                entry = {
                    "where": ew, "prim": name, "out": oi,
                    "dtype": o.dtype.name, "exact": bool(o.exactf),
                    "bound": "unbounded" if m >= INF else int(m),
                }
                for k, v in facts.items():
                    entry[k] = ("unbounded"
                                if isinstance(v, int) and abs(v) >= INF
                                else v)
                if not o.exactf:
                    entry["reason"] = o.fwhy
                ctx.trace_float(entry)


# ---------------------------------------------------------------------------
# Public API.

def _abstract_inputs(closed, in_bounds, in_cong=None):
    """Build input AbstractArrays for a closed jaxpr. in_bounds maps the
    flat input position to either None (full lane range), a (lo, hi)
    tuple, or a per-axis0-row list of (lo, hi). in_cong maps the flat
    input position to a congruence fact: an (m, r) pair (uniform) or a
    per-axis0-row list of (m, r) / None."""
    avs = []
    for i, var in enumerate(closed.jaxpr.invars):
        aval = var.aval
        spec = in_bounds.get(i) if in_bounds else None
        if spec is None:
            av = full_range(aval.shape, aval.dtype)
        elif isinstance(spec, tuple):
            av = mk(aval.shape, aval.dtype, [[spec]])
        else:
            cells = [[(int(lo), int(hi))] for lo, hi in spec]
            av = mk(aval.shape, aval.dtype, cells)
        cspec = in_cong.get(i) if in_cong else None
        if cspec is not None:
            rows = [cspec] if isinstance(cspec, tuple) else list(cspec)
            av.cong = [None if f is None else _cong_norm(f[0], f[1])
                       for f in rows]
        avs.append(av)
    return avs


def analyze_closed(closed, name: str, in_bounds=None,
                   out_within=None, in_cong=None) -> Report:
    """Run both passes (interval prover + determinism/allowlist gate) over
    a ClosedJaxpr. Returns a Report; report.ok is the gate."""
    report = Report(name=name)
    ctx = _Ctx(report)
    interp = _Interp(ctx)
    args = _abstract_inputs(closed, in_bounds, in_cong=in_cong)
    try:
        outs = interp.eval_closed(closed, args, name)
    except Exception as e:
        ctx.violate("internal", name,
                    f"analysis aborted: {type(e).__name__}: {e}")
        return report
    for i, o in enumerate(outs):
        o2 = ctx.observe(o, f"{name}/out{i}", "kernel output")
        if _dkind(o.dtype)[0] == "float" and not o.exactf:
            why = f" [{o.fwhy}]" if o.fwhy else ""
            ctx.violate(
                "float", f"{name}/out{i}",
                "unproven f32 value reaches a consensus-visible "
                f"output{why}",
            )
        report.out_bounds.append(o.rows0() if o.shape else [o.joined()])
        n_rows = o.shape[0] if o.shape else 1
        report.out_cong.append(
            _cong_expand(o.cong, n_rows) if o.cong is not None
            and n_rows <= ROW_CAP else [None] * min(n_rows, ROW_CAP))
        if out_within is not None and i < len(out_within) \
                and out_within[i] is not None:
            hand = out_within[i]
            derived = o2.rows0() if o2.shape else [o2.joined()]
            if len(hand) == len(derived):
                for r, ((lo, hi), hb) in enumerate(zip(derived, hand, strict=True)):
                    if isinstance(hb, tuple):
                        hlo, hhi = hb
                    else:
                        hlo, hhi = 0, int(hb)
                    if lo < hlo or hi > hhi:
                        ctx.violate(
                            "overflow", f"{name}/out{i}[{r}]",
                            f"hand-tracked bound [{hlo}, {hhi}] understates "
                            f"derived interval [{lo}, {hi}]: the Bounds "
                            "bookkeeping in ops/limbs.py is wrong for this "
                            "op — fix the hand bound, not the analyzer",
                        )
            else:
                ctx.violate(
                    "internal", f"{name}/out{i}",
                    f"hand bound has {len(hand)} rows, derived has "
                    f"{len(derived)}")
    return report


def analyze(fn, args, name: str, in_bounds=None, out_within=None,
            static_argnums=(), in_cong=None) -> Report:
    """Trace `fn` at example `args` (concrete or ShapeDtypeStruct) and
    analyze the resulting jaxpr."""
    closed = jax.make_jaxpr(fn, static_argnums=static_argnums)(*args)
    return analyze_closed(closed, name, in_bounds=in_bounds,
                          out_within=out_within, in_cong=in_cong)


# ---------------------------------------------------------------------------
# Sum-of-products refinement.
#
# Pure interval arithmetic cannot prove the Karatsuba combine: in
# z1 = S - z0 - z2 the three operands are correlated (each is a sum of
# products of the SAME input limbs), and the interval of the difference
# explodes even though the true value is the small cross convolution.
# This layer tracks, alongside the interval cells, an optional exact
# decomposition of each integer array as
#
#     value[row, ...] = sum_m coeff_m(row) * monomial_m
#
# where a monomial is a product of at most two interval "atoms" (an atom
# is one limb-row of some earlier array, minted lazily the first time a
# value is sliced into or multiplied). add/sub merge coefficient dicts,
# so S - z0 - z2 cancels the square terms ALGEBRAICALLY and the derived
# bound of z1 is the true cross-term bound — the same argument
# `_kara_combine`'s hand bookkeeping makes, re-derived independently.
# Any op without an exact transfer (shifts, bitwise, compares, reduces)
# simply drops the decomposition; the interval cells always remain.

_ATOM_UID = [0]


class _Atom:
    __slots__ = ("uid", "lo", "hi")

    def __init__(self, lo: int, hi: int):
        _ATOM_UID[0] += 1
        self.uid = _ATOM_UID[0]
        self.lo = lo
        self.hi = hi


_POLY_MAX_TERMS = 6000


def _ensure_poly(av: AbstractArray):
    """Mint a degree-1 decomposition for an integer array that has none:
    one atom per axis-0 row (its interval = the row hull). Sound because
    every cell of row r genuinely lies in that row's interval, and all
    later elementwise ops act row-aligned."""
    if av.poly is not None:
        return av.poly
    if _dkind(av.dtype)[0] != "int":
        return None
    lo, hi = av.joined()
    if lo == hi:
        av.poly = {(): {None: lo}} if lo else {}
        return av.poly
    if av.shape and 1 <= av.shape[0] <= ROW_CAP:
        rows = av.rows0()
        poly: Dict = {}
        for r, (rlo, rhi) in enumerate(rows):
            if rlo == rhi:
                if rlo:
                    poly.setdefault((), {})[r] = rlo
            else:
                poly[(_Atom(rlo, rhi),)] = {r: 1}
    else:
        poly = {(_Atom(lo, hi),): {None: 1}}
    av.poly = poly
    return poly


def _poly_size(p) -> int:
    return sum(len(rows) for rows in p.values())


def _mono_bound(mono) -> Tuple[int, int]:
    lo, hi = 1, 1
    for a in mono:
        cands = (lo * a.lo, lo * a.hi, hi * a.lo, hi * a.hi)
        lo, hi = min(cands), max(cands)
    return lo, hi


def _poly_row_bound(p, r) -> Tuple[int, int]:
    lo = hi = 0
    for mono, rows in p.items():
        c = rows.get(None, 0) + (rows.get(r, 0) if r is not None else 0)
        if not c:
            continue
        mlo, mhi = _mono_bound(mono)
        if c > 0:
            lo += c * mlo
            hi += c * mhi
        else:
            lo += c * mhi
            hi += c * mlo
    return lo, hi


def _poly_addsub(pa, pb, sign: int):
    res = {m: dict(rows) for m, rows in pa.items()}
    for mono, rows in pb.items():
        dst = res.setdefault(mono, {})
        for r, c in rows.items():
            nc = dst.get(r, 0) + sign * c
            if nc:
                dst[r] = nc
            elif r in dst:
                del dst[r]
        if not dst:
            del res[mono]
    if _poly_size(res) > _POLY_MAX_TERMS:
        return None
    return res


def _poly_mul(pa, pb):
    res: Dict = {}
    for ma, ra in pa.items():
        for mb, rb in pb.items():
            if len(ma) + len(mb) > 2:
                return None  # degree > 2: out of the domain, drop exactly
            mono = tuple(sorted(ma + mb, key=lambda a: a.uid))
            dst = res.setdefault(mono, {})
            for r1, c1 in ra.items():
                for r2, c2 in rb.items():
                    if r1 is None:
                        r = r2
                    elif r2 is None or r1 == r2:
                        r = r1
                    else:
                        return None  # row-crossed product: not elementwise
                    nc = dst.get(r, 0) + c1 * c2
                    if nc:
                        dst[r] = nc
                    elif r in dst:
                        del dst[r]
            if not dst:
                del res[mono]
    if _poly_size(res) > _POLY_MAX_TERMS:
        return None
    return res


def _materialize_rows(p, n: int):
    """Expand row=None ('every row') entries to explicit rows 0..n-1 —
    required before pads/concats where 'every row' changes meaning."""
    res: Dict = {}
    for mono, rows in p.items():
        dst: Dict = {}
        for r, c in rows.items():
            if r is None:
                for i in range(n):
                    dst[i] = dst.get(i, 0) + c
            else:
                dst[r] = dst.get(r, 0) + c
        dst = {r: c for r, c in dst.items() if c}
        if dst:
            res[mono] = dst
    if _poly_size(res) > _POLY_MAX_TERMS:
        return None
    return res


def _refine_with_poly(av: AbstractArray):
    """Intersect av's interval cells with its poly-derived row bounds
    (both are sound, so the intersection is)."""
    p = av.poly
    if p is None:
        return
    n = av.shape[0] if av.shape else 1
    if av.shape and (n == 0 or n > ROW_CAP):
        return
    r1 = av.r1
    cells = []
    for i in range(n):
        plo, phi = _poly_row_bound(p, i if av.shape else None)
        row = []
        for j in range(r1):
            clo, chi = av.cell(i, j)
            lo, hi = max(clo, plo), min(chi, phi)
            if lo > hi:  # defensive: both sound => should not happen
                lo, hi = plo, phi
            row.append((_sat(lo), _sat(hi)))
        cells.append(row)
    av.cells = _collapse_if_uniform(cells)


def _rows_aligned(p, av, out):
    """Re-key an operand poly so its rows line up with the result of a
    (possibly broadcasting) elementwise op: a size-1 or absent leading
    axis becomes row=None ('every row'); otherwise the leading axes must
    match. Returns None when alignment can't be established."""
    if p is None:
        return None
    if not av.shape or av.shape[0] == 1:
        folded: Dict = {}
        for mono, rows in p.items():
            dst: Dict = {}
            for r, c in rows.items():
                if r in (None, 0):
                    dst[None] = dst.get(None, 0) + c
                else:
                    return None
            dst = {k: v for k, v in dst.items() if v}
            if dst:
                folded[mono] = dst
        return folded
    if out.shape and av.shape[0] == out.shape[0] \
            and len(av.shape) == len(out.shape):
        return p
    if all(r is None for rows in p.values() for r in rows):
        return p
    return None


def _complementary_support(x, y):
    """True when the two same-shaped arrays never overlap: every tracked
    cell is exactly (0, 0) on at least one side. This is the signature of
    an associative-scan interleave (even/odd positions padded with zeros
    and merged by one add)."""
    if x.shape != y.shape or not x.shape or x.shape[0] > ROW_CAP:
        return False
    ncols = min(x.shape[1], ROW_CAP) if len(x.shape) > 1 else 1
    for i in range(x.shape[0]):
        for j in range(ncols):
            if x.cell(i, j) != (0, 0) and y.cell(i, j) != (0, 0):
                return False
    return True


def _poly_transfer(eqn, ins, outs):
    """Attach exact decompositions to the outputs of structure-preserving
    integer ops; refine their interval cells. Pure precision layer — any
    unsupported case just leaves poly=None."""
    if len(outs) != 1:
        return
    out = outs[0]
    if _dkind(out.dtype)[0] != "int" or (out.shape and out.shape[0] > ROW_CAP
                                         and len(out.shape) != 1):
        return
    name = eqn.primitive.name
    p = None
    try:
        if name == "mul":
            pa = _rows_aligned(_ensure_poly(ins[0]), ins[0], out)
            pb = _rows_aligned(_ensure_poly(ins[1]), ins[1], out)
            if pa is not None and pb is not None:
                p = _poly_mul(pa, pb)
        elif name in ("add", "sub"):
            pa = _rows_aligned(_ensure_poly(ins[0]), ins[0], out)
            pb = _rows_aligned(_ensure_poly(ins[1]), ins[1], out)
            if pa is not None and pb is not None:
                p = _poly_addsub(pa, pb, 1 if name == "add" else -1)
        elif name == "neg":
            pa = _ensure_poly(ins[0])
            if pa is not None:
                p = _poly_addsub({}, pa, -1)
        elif name == "slice":
            starts = eqn.params["start_indices"]
            strides = eqn.params.get("strides") or (1,) * len(starts)
            pa = _ensure_poly(ins[0])
            if pa is not None and out.shape:
                s0, st0, n0 = starts[0], strides[0], out.shape[0]
                p = {}
                for mono, rows in pa.items():
                    dst = {}
                    for r, c in rows.items():
                        if r is None:
                            dst[None] = dst.get(None, 0) + c
                        elif (r - s0) % st0 == 0 and 0 <= (r - s0) // st0 < n0:
                            nr = (r - s0) // st0
                            dst[nr] = dst.get(nr, 0) + c
                    dst = {r: c for r, c in dst.items() if c}
                    if dst:
                        p[mono] = dst
        elif name == "squeeze":
            pa = ins[0].poly
            if pa is not None:
                dims = eqn.params["dimensions"]
                if 0 in dims:
                    p = {}
                    for mono, rows in pa.items():
                        c = rows.get(None, 0) + rows.get(0, 0)
                        if c:
                            p[mono] = {None: c}
                else:
                    p = pa
        elif name == "broadcast_in_dim":
            pa = ins[0].poly
            if pa is not None:
                bdims = eqn.params["broadcast_dimensions"]
                src = ins[0]
                if src.shape and src.shape[0] == 1:
                    pa = {m: {(None if r in (0, None) else r): c
                              for r, c in rows.items()}
                          for m, rows in pa.items()}
                if bdims and bdims[0] == 0 and src.shape \
                        and src.shape[0] == out.shape[0]:
                    p = pa
                elif all(r is None for rows in pa.values() for r in rows):
                    p = pa
        elif name == "pad":
            cfg = eqn.params["padding_config"]
            if (ins[1].joined() == (0, 0) and ins[0].shape
                    and all(c == (0, 0, 0) for c in cfg[1:])
                    and cfg[0][2] == 0 and ins[0].shape[0] <= ROW_CAP):
                pa = _ensure_poly(ins[0])
                if pa is not None:
                    pa = _materialize_rows(pa, ins[0].shape[0])
                    if pa is not None:
                        lo = cfg[0][0]
                        p = {}
                        for mono, rows in pa.items():
                            dst = {r + lo: c for r, c in rows.items()
                                   if 0 <= r + lo < out.shape[0]}
                            if dst:
                                p[mono] = dst
        elif name == "concatenate":
            if eqn.params["dimension"] == 0 and out.shape[0] <= ROW_CAP:
                p = {}
                off = 0
                for o in ins:
                    po = _ensure_poly(o)
                    po = (_materialize_rows(po, o.shape[0])
                          if po is not None else None)
                    if po is None:
                        p = None
                        break
                    for mono, rows in po.items():
                        dst = p.setdefault(mono, {})
                        for r, c in rows.items():
                            dst[r + off] = dst.get(r + off, 0) + c
                    off += o.shape[0]
    except Exception:
        p = None
    if p is not None:
        dominated = False
        if name == "add" and len(ins) == 2 and out.shape \
                and 1 <= out.shape[0] <= ROW_CAP \
                and _complementary_support(ins[0], ins[1]):
            # The associative-scan interleave: an add of two arrays padded
            # onto complementary positions, so every cell holds ONE operand
            # and the other side is exactly zero there. The per-cell grid
            # sees that (cell bound = the one live operand) but the
            # row-keyed poly cannot -- its row bound is the SUM of both
            # operands' row hulls, doubling every bound, and the loose poly
            # then poisons every downstream product. Drop the poly when it
            # is strictly wider than the interval cells on some row and
            # tighter nowhere; re-minted per-row atoms from the cells
            # dominate it for every use. The complementary-support guard is
            # load-bearing: an ordinary add (e.g. Karatsuba's a0 + a1, both
            # halves live in every cell) may also look row-dominated when
            # the operands have column structure, yet its poly carries the
            # atoms the later m - z0 - z1 cancellation needs.
            rows = out.rows0()
            for r in range(out.shape[0]):
                plo, phi = _poly_row_bound(p, r)
                clo, chi = rows[r]
                if plo > clo or phi < chi:
                    dominated = False
                    break
                if plo < clo or phi > chi:
                    dominated = True
        if not dominated:
            out.poly = p
            _refine_with_poly(out)


# ---------------------------------------------------------------------------
# Congruence refinement.
#
# Alongside each interval cell grid, an AbstractArray may carry per-row
# congruence facts x ≡ r (mod m) (m == 0: exactly r). The facts flow
# through the integer ops the scalar-recoding pipeline is built from —
# add/sub/neg, mul, shifts by exact amounts, masking, or-of-disjoint-
# support, reductions, and the structural ops — with gcd-based joins, so
# the analyzer can certify place-value structure (a weighted bit plane
# b_i * 2^i is ≡ 0 mod 2^i; a partial recombination sum of planes i >= t
# is ≡ 0 mod 2^t) that pure intervals cannot express. Any unsupported
# op drops the fact (always sound); widening constructs fresh
# AbstractArrays and so drops facts automatically. The exact-recombination
# theorems of analysis/scalar_check.py use this domain for the modular
# layer of the digit-recoding certificates.

def _cong_norm(m: int, r: int):
    """Normalize a fact: m >= 0; m == 1 carries no information (None);
    m == 0 means exactly r; otherwise reduce r mod m."""
    m = abs(int(m))
    r = int(r)
    if m == 1:
        return None
    if m == 0:
        return (0, r)
    return (m, r % m)


def _cong_join(fa, fb):
    """Weakest fact implied by both: gcd(m1, m2, r1 - r2)."""
    if fa is None or fb is None:
        return None
    (m1, r1), (m2, r2) = fa, fb
    return _cong_norm(math.gcd(math.gcd(m1, m2), abs(r1 - r2)), r1)


def _cong_expand(rows, n: int):
    """Expand a fact list to exactly n per-row entries (len-1 = uniform)."""
    if rows is None:
        return [None] * n
    if len(rows) == n:
        return list(rows)
    if len(rows) == 1:
        return [rows[0]] * n
    return [None] * n


def _cong_add(fa, fb, sign=1):
    if fa is None or fb is None:
        return None
    (m1, r1), (m2, r2) = fa, fb
    return _cong_norm(math.gcd(m1, m2), r1 + sign * r2)


def _cong_mul(fa, fb):
    """(r1 + a·m1)(r2 + b·m2) ≡ r1·r2 mod gcd(m1·m2, m1·r2, m2·r1).
    A factless operand is (1, 0) — any integer ≡ 0 (mod 1) — so a
    product with an exactly-known factor still yields x·c ≡ 0 (mod c)."""
    if fa is None:
        fa = (1, 0)
    if fb is None:
        fb = (1, 0)
    (m1, r1), (m2, r2) = fa, fb
    return _cong_norm(
        math.gcd(math.gcd(m1 * m2, abs(m1 * r2)), abs(m2 * r1)), r1 * r2)


def _cong_exact_rows(av: AbstractArray, n: int):
    """Per-row exactly-known values (from facts with m == 0), else None."""
    rows = _cong_expand(av.cong, n)
    return [r[1] if (r is not None and r[0] == 0) else None for r in rows]


def _cong_rows_for(av: AbstractArray, out: AbstractArray, n: int):
    """Operand facts aligned to the result's n axis-0 rows under
    elementwise broadcasting: a scalar / size-1-leading operand is
    uniform; a same-leading-length operand maps row to row."""
    if av.cong is None:
        return [None] * n
    if not av.shape or av.shape[0] == 1:
        return [av.cong[0]] * n
    if out.shape and av.shape[0] == out.shape[0] and len(av.cong) in (1, n):
        return _cong_expand(av.cong, n)
    if len(av.cong) == 1:
        return [av.cong[0]] * n
    return [None] * n


def _row_hull(av: AbstractArray, i: int):
    lo = min(av.cell(i, j)[0] for j in range(max(av.r1, 1)))
    hi = max(av.cell(i, j)[1] for j in range(max(av.r1, 1)))
    return lo, hi


def _cong_transfer(eqn, ins, outs):
    """Attach congruence facts to the output of supported integer ops.
    Pure precision layer: every unsupported case leaves cong=None."""
    if len(outs) != 1:
        return
    out = outs[0]
    if _dkind(out.dtype)[0] not in ("int", "uint", "bool"):
        return
    n = out.shape[0] if out.shape else 1
    if n == 0 or n > ROW_CAP:
        n = 1 if not out.shape else n
        if n > ROW_CAP:
            return
    name = eqn.primitive.name
    rows = None
    try:
        if name in ("add", "sub"):
            ra = _cong_rows_for(ins[0], out, n)
            rb = _cong_rows_for(ins[1], out, n)
            sign = 1 if name == "add" else -1
            rows = [_cong_add(a, b, sign) for a, b in zip(ra, rb)]
        elif name == "neg":
            ra = _cong_rows_for(ins[0], out, n)
            rows = [None if f is None else _cong_norm(f[0], -f[1])
                    for f in ra]
        elif name == "mul":
            ra = _cong_rows_for(ins[0], out, n)
            rb = _cong_rows_for(ins[1], out, n)
            rows = [None if (a is None and b is None) else _cong_mul(a, b)
                    for a, b in zip(ra, rb)]
        elif name == "shift_left":
            ra = _cong_rows_for(ins[0], out, n)
            sh = _cong_exact_rows(ins[1], n) if ins[1].cong is not None \
                else [None] * n
            rows = [
                None if (s is None or not 0 <= s < 64)
                else _cong_mul(a, (0, 1 << s))
                for a, s in zip(ra, sh)
            ]
        elif name in ("shift_right_logical", "shift_right_arithmetic"):
            # x >> c with 2^c | m and 2^c | r and x >= 0: then 2^c | x,
            # the shift is an exact division, and x/2^c ≡ r/2^c (m/2^c).
            ra = _cong_rows_for(ins[0], out, n)
            sh = _cong_exact_rows(ins[1], n) if ins[1].cong is not None \
                else [None] * n
            rows = []
            for i, (a, s) in enumerate(zip(ra, sh)):
                f = None
                if a is not None and s is not None and 0 <= s < 64:
                    m, r = a
                    lo, _ = _row_hull(ins[0], i if ins[0].r0 > 1 else 0)
                    if lo >= 0 and m % (1 << s) == 0 and r % (1 << s) == 0:
                        f = _cong_norm(m >> s, r >> s)
                rows.append(f)
        elif name == "and":
            # x & (2^t - 1) on x >= 0 is x mod 2^t; with 2^t | m that
            # residue is exactly r mod 2^t.
            for xi, mi in ((0, 1), (1, 0)):
                mask_rows = _cong_exact_rows(ins[mi], n) \
                    if ins[mi].cong is not None else [None] * n
                xa = _cong_rows_for(ins[xi], out, n)
                got = []
                for i, (f, msk) in enumerate(zip(xa, mask_rows)):
                    g = None
                    if (f is not None and msk is not None and msk >= 0
                            and (msk & (msk + 1)) == 0):
                        t = msk.bit_length()
                        m, r = f
                        lo, _ = _row_hull(ins[xi],
                                          i if ins[xi].r0 > 1 else 0)
                        if lo >= 0 and (m % (1 << t) == 0 or m == 0):
                            g = (0, r % (1 << t))
                    got.append(g)
                if any(g is not None for g in got):
                    rows = got
                    break
        elif name == "or":
            # Disjoint-support or is add: y's low t bits provably zero
            # (2^t | m and 2^t | r) and 0 <= x < 2^t (cells), or
            # symmetrically.
            for xi, yi in ((0, 1), (1, 0)):
                xa = _cong_rows_for(ins[xi], out, n)
                ya = _cong_rows_for(ins[yi], out, n)
                got = []
                for i, (fx, fy) in enumerate(zip(xa, ya)):
                    g = None
                    if fy is not None:
                        my, ry = fy
                        lo, hi = _row_hull(ins[xi],
                                           i if ins[xi].r0 > 1 else 0)
                        if lo >= 0 and hi >= 0:
                            t = hi.bit_length()
                            if (my % (1 << t) == 0 or my == 0) \
                                    and ry % (1 << t) == 0 \
                                    and (my != 0 or ry % (1 << t) == 0):
                                g = _cong_add(fx if fx is not None
                                              else (1, 0), fy)
                    got.append(g)
                if any(g is not None for g in got):
                    rows = got
                    break
        elif name == "convert_element_type":
            # Safe only when the conversion cannot wrap: the input
            # interval must fit the target lane.
            if _dkind(ins[0].dtype)[0] in ("int", "uint", "bool"):
                kind, bits = _dkind(out.dtype)
                lo_l = -(1 << (bits - 1)) if kind == "int" else 0
                hi_l = (1 << (bits - 1)) - 1 if kind == "int" \
                    else (1 << bits) - 1
                glo, ghi = ins[0].joined()
                if lo_l <= glo and ghi <= hi_l:
                    rows = _cong_rows_for(ins[0], out, n)
        elif name == "broadcast_in_dim":
            src = ins[0]
            if src.cong is not None:
                bdims = eqn.params["broadcast_dimensions"]
                if not src.shape or src.shape[0] == 1 or len(src.cong) == 1:
                    rows = [src.cong[0]] * n
                elif bdims and bdims[0] == 0 and out.shape \
                        and src.shape[0] == out.shape[0]:
                    rows = _cong_expand(src.cong, n)
                else:
                    # Every output element is some input element, so the
                    # join over all source rows is always sound.
                    acc = src.cong[0]
                    for f in src.cong[1:]:
                        acc = _cong_join(acc, f)
                    if acc is not None:
                        rows = [acc] * n
        elif name in ("reshape", "squeeze", "transpose", "rev",
                      "copy", "stop_gradient"):
            # Layout changes permute/forward elements: a uniform fact
            # survives as-is, a per-row fact survives as the rows' join.
            if ins[0].cong is not None:
                acc = ins[0].cong[0]
                for f in ins[0].cong[1:]:
                    acc = _cong_join(acc, f)
                if acc is not None:
                    rows = [acc] * n
        elif name == "slice":
            src = ins[0]
            if src.cong is not None and out.shape:
                if len(src.cong) == 1:
                    rows = [src.cong[0]] * n
                else:
                    starts = eqn.params["start_indices"]
                    strides = eqn.params.get("strides") \
                        or (1,) * len(starts)
                    s0, st0 = starts[0], strides[0]
                    full = _cong_expand(src.cong, src.shape[0])
                    rows = [full[s0 + k * st0] for k in range(out.shape[0])]
        elif name == "concatenate":
            if eqn.params["dimension"] == 0 and out.shape \
                    and out.shape[0] <= ROW_CAP:
                rows = []
                for o in ins:
                    rows.extend(_cong_expand(o.cong, o.shape[0]))
        elif name == "reduce_sum":
            axes = eqn.params["axes"]
            src = ins[0]
            if src.cong is not None and src.shape:
                k_other = 1
                for ax in axes:
                    if ax != 0:
                        k_other *= src.shape[ax]
                full = _cong_expand(src.cong, src.shape[0])
                # each row's sum: k_other elements per row index ≡ k·r
                per_row = [None if f is None
                           else _cong_mul(f, (0, k_other))
                           for f in full]
                if 0 in axes:
                    acc = per_row[0]
                    for f in per_row[1:]:
                        acc = _cong_add(acc, f)
                    rows = [acc] * n
                elif out.shape and out.shape[0] == src.shape[0]:
                    rows = _cong_expand(per_row, n)
    except Exception:
        rows = None
    if rows is not None and any(f is not None for f in rows):
        if len(rows) not in (1, n):
            return
        out.cong = rows
        _refine_with_cong(out)


def _refine_with_cong(av: AbstractArray):
    """Tighten interval cells to the nearest values satisfying the row's
    congruence fact (both layers are sound, so the intersection is)."""
    if av.cong is None:
        return
    n = av.shape[0] if av.shape else 1
    if av.shape and (n == 0 or n > ROW_CAP):
        return
    facts = _cong_expand(av.cong, max(av.r0, 1))
    if len(facts) != av.r0:
        return
    cells = []
    changed = False
    for i, row in enumerate(av.cells):
        f = facts[i]
        if f is None:
            cells.append(list(row))
            continue
        m, r = f
        new_row = []
        for lo, hi in row:
            if m == 0:
                if lo <= r <= hi:
                    nlo = nhi = r
                else:
                    nlo, nhi = lo, hi  # defensive; keep sound cells
            else:
                nlo = lo + ((r - lo) % m)
                nhi = hi - ((hi - r) % m)
                if nlo > nhi:
                    nlo, nhi = lo, hi
            changed = changed or (nlo, nhi) != (lo, hi)
            new_row.append((nlo, nhi))
        cells.append(new_row)
    if changed:
        av.cells = _collapse_if_uniform(cells)
