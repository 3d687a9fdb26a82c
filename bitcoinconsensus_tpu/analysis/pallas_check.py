"""Pallas-level consensus prover: the interval engine below the jaxpr.

PR 1's analyzer certifies every XLA-path kernel, but the hand-fused
Mosaic kernel (`ops/pallas_kernel.py`) — the code that actually runs the
hot path on TPU — was vetted only by bit-equality spot tests. This
module closes that gap by teaching `analysis/interval.py` the Pallas
dialect, in three layers:

1. **Abstract Ref semantics.** A `pallas_call` equation is entered, its
   kernel jaxpr evaluated by the same interval interpreter, with every
   VMEM ref modeled as a `RefAbstract`: a mutable per-axis-0-row store
   of interval abstractions. `get`/`swap`/`addupdate` transfer rules
   thread per-row intervals through the `(16, NLIMB, tile)` scratch
   tables, so the signed-window selects and the batch-inverse
   prefix/suffix trees are proven int32-safe with per-limb precision —
   the same observation discipline as the jaxpr layer, re-derived with
   no access to the kernel's hand bookkeeping. Writes inside loop
   bodies / unresolved cond branches degrade to hull-merges
   (`ctx.in_loop`), keeping strong updates sound; a read of a scratch
   or output row that was never written is a gate failure
   (uninitialized VMEM must not feed a consensus verdict).

2. **Grid/BlockSpec program checks.** Every index map is evaluated
   concretely for every grid step: block windows must stay inside the
   array extent, array dims must divide by block dims (the
   `B % LANE_TILE` contract), and every OUTPUT block offset must be
   produced by exactly one grid step and the set must tile the array —
   "every output element written exactly once". The peak VMEM live set
   (pipelined blocks x double-buffering + scratch + a last-use liveness
   walk over the kernel's intermediates) is computed, attached to the
   `Report` (`vmem_peak_bytes`, `grid`), and budgeted against
   `VMEM_BUDGET_BYTES` (14 MB of the ~16 MB core limit — headroom for
   Mosaic's own spills).

3. **Ref-discipline lint.** Captured array constants in the kernel
   jaxpr are rejected (limb constants must arrive via the
   `set_const_provider` row table, `consts_ref`); i1 vectors and 64-bit
   dtypes through scan/while carries are rejected (Mosaic cannot lower
   vmasks across loop boundaries; the kernel carries int32 0/1 masks).

Scratch persists across grid steps on a real TPU, but the abstract body
is evaluated once per `pallas_call`: a kernel whose step N reads scratch
written by step N-1 is flagged by the read-before-write check. That is
deliberate — grid-step-order dependence is exactly the kind of schedule
coupling the consensus kernel must not have.

Importing this module registers the `get`/`swap`/`addupdate`/
`program_id`/`pallas_call` rules into `interval.RULES`, so a plain
`interval.analyze(verify_tiles, ...)` proves preamble, kernel body and
epilogue end to end. (`interval.ALLOWED_PRIMITIVES` is a frozen
import-time snapshot and intentionally does not grow: state primitives
are only legal inside a Pallas trace, where these rules vet them.)

The exact-float certificate of `interval.py` carries through unchanged:
ref reads/writes preserve `exactf`/`fwhy`, an inexact f32 value written
into VMEM is a gate failure at the write site, and the state primitives
are registered on `interval.FLOAT_VETTED` so the post-pass does not
demote values they merely move.

`NEGATIVES` holds deliberately broken toy kernels (out-of-bounds index
map, read-before-write scratch, an overflowing fe_mul-without-canon
chain, a double-written output block, plus three unsound f32 chains: a
default-precision dot, a 2^24-overflowing accumulation, and a float
round-trip through an unvetted op) used by the tests and
`scripts/consensus_lint.py --negative` to prove the gate actually fires.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import tree_util

from jax.experimental import pallas as pl
from jax.extend import core as jax_core

from . import interval as IV

__all__ = [
    "RefAbstract",
    "VMEM_BYTES",
    "VMEM_BUDGET_BYTES",
    "NEGATIVES",
    "analyze_negative",
    "analyze_positive_toy",
]

VMEM_BYTES = 16 * 1024 * 1024        # per-core VMEM on current TPUs
VMEM_BUDGET_BYTES = 14 * 1024 * 1024  # gate margin: leave Mosaic headroom
MAX_GRID_STEPS = 4096                 # index-map enumeration cap
_DOUBLE_BUFFER = 2                    # Mosaic pipelines grid blocks


def _nbytes(shape, dtype) -> int:
    n = 1
    for s in shape or ():
        n *= int(s)
    return n * max(np.dtype(dtype).itemsize, 1)


def _is_ref_aval(aval) -> bool:
    return "Ref" in type(aval).__name__ or hasattr(aval, "inner_aval")


def _origin(bm, i) -> str:
    return str(getattr(bm, "origin", "") or f"operand{i}")


def _block_dim(b) -> int:
    """Extent of one BlockMapping.block_shape entry: `pl.Blocked(n)` (what
    a plain int in a BlockSpec canonicalizes to) is n; a squeezed dim
    spans one element."""
    if isinstance(b, pl.Blocked):
        return int(b.block_size)
    if isinstance(b, pl.Squeezed):
        return 1
    raise TypeError(f"unsupported block dim {b!r}")


# ---------------------------------------------------------------------------
# RefAbstract: the abstract VMEM ref.


def _row_hull(v: "IV.AbstractArray", i: int) -> Tuple[int, int]:
    lo = min(v.cell(i, j)[0] for j in range(v.r1))
    hi = max(v.cell(i, j)[1] for j in range(v.r1))
    return (lo, hi)


class RefAbstract:
    """Mutable interval store for one VMEM ref.

    Rows along axis 0 (the table/limb/window axis of every consensus
    ref) are tracked individually while `shape[0] <= ROW_CAP`; each row
    holds an AbstractArray of the remainder shape (which itself tracks
    its own leading axis — so a (16, NLIMB, tile) table keeps a full
    (16, NLIMB) interval grid). `None` rows are bottom: never written.
    """

    __slots__ = ("name", "kind", "shape", "dtype", "rest", "n0", "gran",
                 "rows", "writes", "rbw")

    def __init__(self, name, kind, shape, dtype, init=None):
        self.name = name
        self.kind = kind  # "in" | "out" | "scratch"
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.n0 = self.shape[0] if self.shape else 1
        self.rest = self.shape[1:]
        self.gran = self.n0 if 1 <= self.n0 <= IV.ROW_CAP else 1
        self.rows: List[Optional[IV.AbstractArray]] = [None] * self.gran
        self.writes = [0] * self.gran
        self.rbw: Dict[int, str] = {}  # slot -> where of first bottom read
        if init is not None:
            for s in range(self.gran):
                r = s if self.gran == self.n0 else 0
                cells = [[init.cell(r, j)] for j in range(init.r1)]
                self.rows[s] = IV.mk(self.rest, self.dtype, cells,
                                     exactf=init.exactf)

    # -- indexing -----------------------------------------------------------

    def _slot(self, r: int) -> int:
        return r if self.gran == self.n0 else 0

    def _resolve(self, ctx, idx, where):
        """-> (rows, keeps_axis0, trailing_full, exact). `rows` is the
        (clamped) set of axis-0 indices possibly touched; `exact` means
        that set is known precisely (strong updates are legal)."""
        if idx is None or len(idx) != 1 or not self.shape:
            return list(range(self.n0)), True, not self.shape, False
        entries = list(idx[0].indices)
        if not entries:
            return list(range(self.n0)), True, True, True
        keeps, lo, hi, exact = _entry_range(entries[0], self.n0)
        if lo < 0 or hi > self.n0 - 1:
            ctx.violate(
                "ref", where,
                f"{self.kind} ref `{self.name}` axis-0 index interval "
                f"[{lo}, {hi}] out of bounds for {self.n0} rows",
            )
            lo, hi = max(lo, 0), min(hi, self.n0 - 1)
            if lo > hi:
                lo, hi = 0, self.n0 - 1
            exact = False
        trailing_full = all(
            _is_full_slice(e, n)
            for e, n in zip(entries[1:], self.shape[1:])
        ) and len(entries) - 1 <= len(self.shape) - 1
        return list(range(lo, hi + 1)), keeps, trailing_full, exact

    # -- read ---------------------------------------------------------------

    def read(self, ctx, idx, out_shape, out_dtype, where, check_rbw=True):
        rows, keeps, trailing_full, _ = self._resolve(ctx, idx, where)
        slots = sorted({self._slot(r) for r in rows})
        if check_rbw and self.kind in ("out", "scratch"):
            for s in slots:
                if self.rows[s] is None and s not in self.rbw:
                    self.rbw[s] = where
        vals = [self.rows[s] if self.rows[s] is not None
                else IV.full_range(self.rest, self.dtype) for s in slots]
        exactf = all(v.exactf for v in vals) and bool(vals)
        if keeps:
            full = (self.gran == self.n0 and trailing_full
                    and rows == list(range(self.n0))
                    and out_shape and out_shape[0] == self.n0)
            if full:
                rmax = max(v.r0 for v in vals)
                cells = []
                for v in vals:
                    if v.r0 == rmax:
                        cells.append([_row_hull(v, i) for i in range(rmax)])
                    else:
                        cells.append([v.joined()] * rmax)
                return IV.mk(out_shape, out_dtype, cells, exactf=exactf)
            hull = _join_list(vals).joined()
            return IV.mk(out_shape, out_dtype, [[hull]], exactf=exactf)
        joined = _join_list(vals)
        if trailing_full and tuple(out_shape) == tuple(self.rest):
            return joined
        return IV.mk(out_shape, out_dtype, [[joined.joined()]],
                     exactf=exactf)

    # -- write --------------------------------------------------------------

    def write(self, ctx, idx, val, where, weak):
        if self.dtype.kind == "f" and not val.exactf:
            why = f" [{val.fwhy}]" if getattr(val, "fwhy", None) else ""
            ctx.violate(
                "float", where,
                f"inexact float32 value written to {self.kind} ref "
                f"`{self.name}`: every VMEM-resident f32 table must carry "
                f"an exact-integer certificate{why}",
            )
        rows, keeps, trailing_full, exact = self._resolve(ctx, idx, where)
        slots = sorted({self._slot(r) for r in rows})
        full_slice = (keeps and self.gran == self.n0 and trailing_full
                      and rows == list(range(self.n0)))
        strong = (not weak) and exact and self.gran == self.n0 and (
            full_slice or len(rows) == 1)
        for s in slots:
            if full_slice:
                j_hi = max(val.r1, 1)
                cells = [[val.cell(min(s, max(val.r0 - 1, 0)), j)]
                         for j in range(j_hi)]
                rv = IV.mk(self.rest, self.dtype, cells, exactf=val.exactf)
            elif (not keeps and trailing_full
                  and tuple(val.shape) == tuple(self.rest)):
                rv = val
            else:
                rv = IV.mk(self.rest, self.dtype, [[val.joined()]],
                           exactf=val.exactf)
            if strong:
                self.rows[s] = rv
            else:
                cur = self.rows[s]
                self.rows[s] = rv if cur is None else IV.join_values(cur, rv)
            if not ctx.mute:
                self.writes[s] += 1

    # -- export -------------------------------------------------------------

    def to_array(self, shape, dtype) -> "IV.AbstractArray":
        vals = [r if r is not None
                else IV.full_range(self.rest, self.dtype)
                for r in self.rows]
        exactf = all(v.exactf for v in vals)
        if self.gran == self.n0 and shape and shape[0] == self.n0:
            rmax = max(v.r0 for v in vals)
            cells = []
            for v in vals:
                if v.r0 == rmax:
                    cells.append([_row_hull(v, i) for i in range(rmax)])
                else:
                    cells.append([v.joined()] * rmax)
            return IV.mk(shape, dtype, cells, exactf=exactf)
        hull = _join_list(vals).joined()
        return IV.mk(shape, dtype, [[hull]], exactf=exactf)

    def __repr__(self):
        written = sum(r is not None for r in self.rows)
        return (f"RefAbstract({self.name}, {self.kind}, {self.shape}, "
                f"{written}/{self.gran} rows written)")


def _join_list(vals: List["IV.AbstractArray"]) -> "IV.AbstractArray":
    out = vals[0]
    for v in vals[1:]:
        out = IV.join_values(out, v)
    return out


def _entry_range(e, n: int):
    """Classify one NDIndexer dim entry -> (keeps_axis, lo, hi, exact)."""
    if hasattr(e, "start") and hasattr(e, "size"):  # pl.Slice
        size = int(e.size)
        stride = int(getattr(e, "stride", 1) or 1)
        st = e.start
        if isinstance(st, IV.AbstractArray):
            slo, shi = st.joined()
            exact = slo == shi
        elif isinstance(st, (int, np.integer)):
            slo = shi = int(st)
            exact = True
        else:
            return True, 0, n - 1, False
        return True, slo, shi + (size - 1) * stride, exact and stride == 1
    if isinstance(e, IV.AbstractArray):
        lo, hi = e.joined()
        if e.shape:  # advanced integer-array index: keeps a dim, joins
            return True, lo, hi, False
        return False, lo, hi, lo == hi
    if isinstance(e, (int, np.integer)):
        return False, int(e), int(e), True
    return False, 0, n - 1, False


def _is_full_slice(e, n: int) -> bool:
    return (hasattr(e, "start") and hasattr(e, "size")
            and isinstance(e.start, (int, np.integer))
            and int(e.start) == 0 and int(e.size) == int(n)
            and int(getattr(e, "stride", 1) or 1) == 1)


def _indexer(eqn, ins, start: int):
    """Rebuild the NDIndexer list from the flattened dynamic index
    operands (abstract values stand in for the tracers)."""
    tree = eqn.params.get("tree")
    if tree is None:
        return None
    idx = tree_util.tree_unflatten(tree, list(ins[start:]))
    entries = [t for t in idx if hasattr(t, "indices")]
    return entries if entries else None


# ---------------------------------------------------------------------------
# State-primitive transfer rules.


def _r_get(interp, eqn, ins, where):
    out = eqn.outvars[0].aval
    ref = ins[0]
    if not isinstance(ref, RefAbstract):
        interp.ctx.violate("internal", where, "get on a non-ref operand")
        return [IV.top(out.shape, out.dtype)]
    for v in ins[1:]:
        interp.ctx.observe(v, where, "ref index")
    idx = _indexer(eqn, ins, 1)
    return [ref.read(interp.ctx, idx, out.shape, out.dtype, where)]


def _r_swap(interp, eqn, ins, where):
    out = eqn.outvars[0].aval
    ref, val = ins[0], ins[1]
    if not isinstance(ref, RefAbstract):
        interp.ctx.violate("internal", where, "swap on a non-ref operand")
        return [IV.top(out.shape, out.dtype)]
    for v in ins[2:]:
        interp.ctx.observe(v, where, "ref index")
    idx = _indexer(eqn, ins, 2)
    drop = type(eqn.outvars[0]).__name__ == "DropVar"
    old = (IV.top(out.shape, out.dtype) if drop
           else ref.read(interp.ctx, idx, out.shape, out.dtype, where))
    ref.write(interp.ctx, idx, val, where, weak=interp.ctx.in_loop > 0)
    return [old]


def _r_addupdate(interp, eqn, ins, where):
    ref, val = ins[0], ins[1]
    if not isinstance(ref, RefAbstract):
        interp.ctx.violate("internal", where, "addupdate on a non-ref")
        return []
    idx = _indexer(eqn, ins, 2)
    old = ref.read(interp.ctx, idx, val.shape, val.dtype, where)
    acc = IV._ewise(interp.ctx, val.shape, val.dtype, [old, val],
                    lambda x, y: (x[0] + y[0], x[1] + y[1]))
    ref.write(interp.ctx, idx, acc, where, weak=True)
    return []


_GRID_STACK: List[Tuple[int, ...]] = []


def _r_program_id(interp, eqn, ins, where):
    out = eqn.outvars[0].aval
    axis = int(eqn.params.get("axis", 0))
    hi = 0
    if _GRID_STACK and axis < len(_GRID_STACK[-1]):
        hi = max(int(_GRID_STACK[-1][axis]) - 1, 0)
    return [IV.mk(out.shape, out.dtype, [[(0, hi)]])]


# ---------------------------------------------------------------------------
# Grid / BlockSpec program checks.


def _grid_total(grid) -> int:
    t = 1
    for g in grid:
        t *= int(g)
    return t


def _check_grid(ctx, grid, bms, nin, nout, where):
    total = _grid_total(grid)
    steps = list(itertools.islice(
        np.ndindex(*grid) if grid else iter([()]), MAX_GRID_STEPS))
    truncated = total > MAX_GRID_STEPS
    if truncated and not ctx.mute:
        ctx.report.notes.append(
            f"grid has {total} steps; index maps checked for the first "
            f"{MAX_GRID_STEPS} only")
    seen_out: List[Dict[tuple, tuple]] = [dict() for _ in range(nout)]
    for bi, bm in enumerate(bms):
        name = _origin(bm, bi)
        bw = f"{where}/blockspec[{name}]"
        ashape = tuple(int(s) for s in bm.array_aval.shape)
        bshape = tuple(_block_dim(b) for b in bm.block_shape)
        for d, (adim, bdim) in enumerate(zip(ashape, bshape)):
            if bdim and adim % bdim:
                ctx.violate(
                    "grid", bw,
                    f"array dim {d} ({adim}) is not divisible by the block "
                    f"dim ({bdim}): partial tiles are outside the verified "
                    "contract (the B % LANE_TILE == 0 precondition)",
                )
        cj = bm.index_map_jaxpr
        if len(cj.jaxpr.invars) != len(grid):
            ctx.violate(
                "grid", bw,
                f"index map takes {len(cj.jaxpr.invars)} operands for a "
                f"{len(grid)}-d grid (dynamic index operands are not part "
                "of the verified contract)",
            )
            continue
        for step in steps:
            try:
                bidx = jax.core.eval_jaxpr(
                    cj.jaxpr, cj.consts, *[np.int32(v) for v in step])
            except Exception as e:  # index map must be total
                ctx.violate(
                    "grid", bw,
                    f"index map failed at grid step {tuple(step)}: "
                    f"{type(e).__name__}: {e}")
                break
            starts = tuple(int(b) * bd for b, bd in zip(bidx, bshape))
            for d, (st, bd, adim) in enumerate(zip(starts, bshape, ashape)):
                if st < 0 or st + bd > adim:
                    ctx.violate(
                        "grid", bw,
                        f"index map sends grid step {tuple(step)} to block "
                        f"start {st} on dim {d}: window [{st}, {st + bd}) "
                        f"escapes the array extent {adim}",
                    )
            if nin <= bi < nin + nout:
                j = bi - nin
                prev = seen_out[j].get(starts)
                if prev is not None:
                    ctx.violate(
                        "grid", bw,
                        f"output block at offset {starts} is written by grid "
                        f"steps {prev} and {tuple(step)} — every output "
                        "element must be written exactly once",
                    )
                else:
                    seen_out[j][starts] = tuple(step)
        if not truncated and nin <= bi < nin + nout:
            j = bi - nin
            blk = 1
            for b in bshape:
                blk *= max(b, 1)
            tot = 1
            for s in ashape:
                tot *= s
            if len(seen_out[j]) * blk != tot:
                ctx.violate(
                    "grid", bw,
                    f"grid writes {len(seen_out[j])} distinct blocks of "
                    f"{blk} elements but the output has {tot}: some "
                    "elements are never written",
                )


# ---------------------------------------------------------------------------
# VMEM live-set accounting.

_PEAK_CACHE: Dict[int, int] = {}


def _sub_jaxprs(e):
    for v in e.params.values():
        if hasattr(v, "jaxpr") and hasattr(v, "consts"):  # ClosedJaxpr
            yield v.jaxpr
        elif hasattr(v, "eqns") and hasattr(v, "invars"):  # raw Jaxpr
            yield v
        elif isinstance(v, (tuple, list)):
            for u in v:
                if hasattr(u, "jaxpr") and hasattr(u, "consts"):
                    yield u.jaxpr
                elif hasattr(u, "eqns") and hasattr(u, "invars"):
                    yield u


def _peak_live(jaxpr) -> int:
    """Peak bytes of simultaneously-live SSA intermediates, by a
    last-use liveness walk. Sub-jaxprs (scan/while/cond bodies)
    contribute their own internal peak at their call site. Refs are
    excluded (counted as blocks/scratch); a conservative model of what
    Mosaic must hold, not a simulation of its allocator."""
    key = id(jaxpr)
    if key in _PEAK_CACHE:
        return _PEAK_CACHE[key]
    eqns = jaxpr.eqns
    last = {}
    for t, e in enumerate(eqns):
        for v in e.invars:
            if not isinstance(v, jax_core.Literal):
                last[v] = t
    for v in jaxpr.outvars:
        if not isinstance(v, jax_core.Literal):
            last[v] = len(eqns)
    alive: Dict[object, int] = {}
    cur = 0
    for v in jaxpr.constvars:
        if v in last and not _is_ref_aval(v.aval):
            alive[v] = _nbytes(v.aval.shape, v.aval.dtype)
            cur += alive[v]
    peak = cur
    for t, e in enumerate(eqns):
        born_dead = 0
        for v in e.outvars:
            if _is_ref_aval(v.aval):
                continue
            sz = _nbytes(v.aval.shape, v.aval.dtype)
            if type(v).__name__ == "DropVar" or v not in last:
                born_dead += sz  # materialized for this eqn only
                continue
            if v not in alive:
                alive[v] = sz
                cur += sz
        sub = 0
        for sj in _sub_jaxprs(e):
            sub = max(sub, _peak_live(sj))
        if cur + sub + born_dead > peak:
            peak = cur + sub + born_dead
        for v in {v for v in list(e.invars) + list(e.outvars)
                  if not isinstance(v, jax_core.Literal)}:
            if v in alive and last.get(v, -1) <= t:
                cur -= alive.pop(v)
    _PEAK_CACHE[key] = peak
    return peak


def _vmem_peak(jaxpr, bms, grid, nin, nout) -> int:
    dbuf = _DOUBLE_BUFFER if _grid_total(grid) > 1 else 1
    blocks = 0
    for bm in bms:
        bshape = tuple(_block_dim(b) for b in bm.block_shape)
        blocks += _nbytes(bshape, bm.array_aval.dtype)
    scratch = 0
    for v in jaxpr.invars[nin + nout:]:
        aval = v.aval
        scratch += _nbytes(aval.shape, aval.dtype)
    return blocks * dbuf + scratch + _peak_live(jaxpr)


# ---------------------------------------------------------------------------
# Ref-discipline lint.


def _check_carry(ctx, aval, where, i):
    shape = tuple(getattr(aval, "shape", ()) or ())
    try:
        dt = np.dtype(aval.dtype)
    except Exception:
        return
    if dt == np.bool_ and shape:
        ctx.violate(
            "ref", where,
            f"loop carry {i} is an i1 vector {shape}: Mosaic cannot lower "
            "vmask values through loop boundaries — carry int32 0/1 masks "
            "instead (see ops/pallas_kernel.py wbody/gbody)",
        )
    elif dt.itemsize == 8:
        ctx.violate(
            "dtype64", where,
            f"loop carry {i} is 64-bit ({dt}) — banned in consensus kernels",
        )


def _carry_lint(ctx, jaxpr, where):
    for k, e in enumerate(jaxpr.eqns):
        nm = e.primitive.name
        ew = f"{where}#{k}:{nm}"
        if nm == "scan":
            cj = e.params["jaxpr"]
            nc, ncar = e.params["num_consts"], e.params["num_carry"]
            for i, v in enumerate(cj.jaxpr.invars[nc:nc + ncar]):
                _check_carry(ctx, v.aval, ew, i)
            _carry_lint(ctx, cj.jaxpr, ew)
        elif nm == "while":
            bj = e.params["body_jaxpr"]
            bn = e.params["body_nconsts"]
            for i, v in enumerate(bj.jaxpr.invars[bn:]):
                _check_carry(ctx, v.aval, ew, i)
            _carry_lint(ctx, bj.jaxpr, ew)
        else:
            for sj in _sub_jaxprs(e):
                _carry_lint(ctx, sj, ew)


def _ref_discipline(ctx, jaxpr, where):
    for cv in jaxpr.constvars:
        aval = cv.aval
        shape = tuple(getattr(aval, "shape", ()) or ())
        n = 1
        for s in shape:
            n *= int(s)
        if shape and n > 1:
            ctx.violate(
                "ref", f"{where}/constvars",
                f"kernel captured an array constant {shape} {aval.dtype}: "
                "Pallas consensus kernels must source every limb constant "
                "from the consts_ref row table (ops/limbs.set_const_provider"
                "), never closure capture",
            )
    _carry_lint(ctx, jaxpr, where)


# ---------------------------------------------------------------------------
# The pallas_call transfer rule.


def _r_pallas_call(interp, eqn, ins, where):
    ctx = interp.ctx
    p = eqn.params
    gm = p["grid_mapping"]
    kj = p["jaxpr"]
    jaxpr = kj.jaxpr if hasattr(kj, "jaxpr") else kj
    consts = list(getattr(kj, "consts", []) or [])
    grid = tuple(int(g) for g in gm.grid)
    nidx = int(getattr(gm, "num_index_operands", 0))
    nin, nout = int(gm.num_inputs), int(gm.num_outputs)
    nscr = int(gm.num_scratch_operands)
    bms = list(gm.block_mappings)

    for s in ins[:nidx]:
        ctx.observe(s, where, "pallas index operand")
    ops = ins[nidx:]

    _check_grid(ctx, grid, bms, nin, nout, where)
    vmem = _vmem_peak(jaxpr, bms, grid, nin, nout)
    if not ctx.mute:
        rep = ctx.report
        rep.vmem_peak_bytes = max(rep.vmem_peak_bytes or 0, vmem)
        if rep.grid is None:
            rep.grid = grid
    if vmem > VMEM_BUDGET_BYTES:
        ctx.violate(
            "vmem", where,
            f"peak VMEM live set {vmem} bytes (blocks x double-buffer + "
            f"scratch + intermediates) exceeds the {VMEM_BUDGET_BYTES}-byte "
            f"budget (core limit ~{VMEM_BYTES}; the margin is Mosaic "
            "spill headroom)",
        )
    _ref_discipline(ctx, jaxpr, where)

    tops = [IV.top(v.aval.shape, v.aval.dtype) for v in eqn.outvars]
    kin = list(jaxpr.invars)
    if len(kin) != nin + nout + nscr or len(ops) < nin:
        ctx.violate(
            "internal", where,
            f"kernel arity mismatch: {len(kin)} invars vs "
            f"{nin}+{nout}+{nscr} declared operands")
        return tops
    if jaxpr.constvars and len(consts) != len(jaxpr.constvars):
        # Already flagged by _ref_discipline; body cannot be evaluated
        # faithfully without the constants.
        return tops

    refs: List[RefAbstract] = []
    for i in range(nin):
        aval = kin[i].aval
        refs.append(RefAbstract(
            _origin(bms[i], i), "in", aval.shape, aval.dtype,
            init=_block_abs(ops[i], aval)))
    for j in range(nout):
        aval = kin[nin + j].aval
        refs.append(RefAbstract(
            _origin(bms[nin + j], nin + j), "out", aval.shape, aval.dtype))
    for s in range(nscr):
        aval = kin[nin + nout + s].aval
        refs.append(RefAbstract(
            f"scratch{s}", "scratch", aval.shape, aval.dtype))

    closed = jax_core.ClosedJaxpr(jaxpr, consts)
    _GRID_STACK.append(grid)
    try:
        interp.eval_closed(closed, list(refs), where + "/kernel")
    finally:
        _GRID_STACK.pop()

    # Read-before-write findings are recorded on first encounter (even
    # under fixpoint warmup, where ctx.violate is muted — program order
    # of the first abstract pass matches the first concrete iteration).
    for ref in refs:
        for slot, rw in sorted(ref.rbw.items()):
            ctx.violate(
                "ref", rw,
                f"read of {ref.kind} ref `{ref.name}` row {slot} before any "
                "write: uninitialized VMEM must not feed a consensus "
                "verdict",
            )

    outs = []
    for j in range(nout):
        ref = refs[nin + j]
        missing = [s for s in range(ref.gran) if ref.rows[s] is None]
        if missing:
            ctx.violate(
                "ref", f"{where}/kernel",
                f"output ref `{ref.name}` rows {missing} are never written",
            )
        out_aval = eqn.outvars[j].aval
        outs.append(ref.to_array(out_aval.shape, out_aval.dtype))
    return outs


def _block_abs(op: "IV.AbstractArray", aval) -> "IV.AbstractArray":
    """Slice an operand abstraction down to one block: axes the block
    spans fully keep their tracked rows, partial axes join (sound for
    every grid step, since the hull covers the whole operand)."""
    shape = tuple(int(s) for s in aval.shape)
    if op is None:
        return IV.full_range(shape, aval.dtype)
    keep0 = bool(op.shape and shape and op.shape[0] == shape[0])
    keep1 = bool(len(op.shape) > 1 and len(shape) > 1
                 and op.shape[1] == shape[1])
    return IV.take_axes(op, shape, 0 if keep0 else None,
                        1 if keep1 else None)


IV.RULES["get"] = _r_get
IV.RULES["swap"] = _r_swap
IV.RULES["addupdate"] = _r_addupdate
IV.RULES["program_id"] = _r_program_id
IV.RULES["pallas_call"] = _r_pallas_call

# The state primitives move values without float arithmetic (get/swap
# return the refs' own certificates; addupdate and pallas_call results
# are re-checked at the ref layer above), so they preserve the carried
# exact-float certificate rather than demoting it.
IV.FLOAT_VETTED.update({"get", "swap", "addupdate", "pallas_call",
                        "program_id"})


# ---------------------------------------------------------------------------
# Toy kernels: the gate must demonstrably fire. Each builder returns
# (fn, arg_specs, in_bounds); shapes are trace-only (never compiled).

_TOY_TILE = 128


def _toy_specs(rows, tile, index_map=None):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((rows, tile), index_map or (lambda i: (0, i)))


def _build_positive():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[:] = x_ref[:] + 1

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[_toy_specs(8, _TOY_TILE)],
            out_specs=_toy_specs(8, _TOY_TILE),
            out_shape=jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),
        )(x)

    args = (jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),)
    return fn, args, {0: (0, 100)}


def _build_oob_index_map():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[:] = x_ref[:]

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            # Off-by-one block index: the last grid step's window escapes.
            in_specs=[_toy_specs(8, _TOY_TILE, lambda i: (0, i + 1))],
            out_specs=_toy_specs(8, _TOY_TILE),
            out_shape=jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),
        )(x)

    args = (jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),)
    return fn, args, {0: (0, 100)}


def _build_read_before_write():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(x_ref, o_ref, s_ref):
        # s_ref row 0 is read but never written anywhere.
        o_ref[:] = x_ref[:] + s_ref[0][None, :]

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[_toy_specs(8, _TOY_TILE)],
            out_specs=_toy_specs(8, _TOY_TILE),
            out_shape=jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),
            scratch_shapes=[pltpu.VMEM((4, _TOY_TILE), jnp.int32)],
        )(x)

    args = (jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),)
    return fn, args, {0: (0, 100)}


def _build_mul_overflow():
    from jax.experimental import pallas as pl
    from ..ops import limbs as L

    def kern(x_ref, o_ref):
        # fe_mul's convolution is int32-safe only under the 13-bit weak
        # contract; 14-bit inputs without a canon overflow it.
        o_ref[:] = L.fe_mul(x_ref[:], x_ref[:])

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[_toy_specs(L.NLIMB, _TOY_TILE)],
            out_specs=_toy_specs(L.NLIMB, _TOY_TILE),
            out_shape=jax.ShapeDtypeStruct(
                (L.NLIMB, 2 * _TOY_TILE), jnp.int32),
        )(x)

    args = (jax.ShapeDtypeStruct((L.NLIMB, 2 * _TOY_TILE), jnp.int32),)
    return fn, args, {0: [(0, (1 << 14) - 1)] * L.NLIMB}


def _build_double_write():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[:] = x_ref[:]

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[_toy_specs(8, _TOY_TILE)],
            # Both grid steps write output block 0; block 1 never written.
            out_specs=_toy_specs(8, _TOY_TILE, lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),
        )(x)

    args = (jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),)
    return fn, args, {0: (0, 100)}


def _build_f32_default_precision_dot():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        xf = x_ref[:].astype(jnp.float32)
        # Missing precision=HIGHEST: the MXU's default f32 path goes
        # through bfloat16 passes, so the products may round.
        y = jax.lax.dot_general(xf, xf, (((1,), (1,)), ((), ())))
        o_ref[:] = jnp.broadcast_to(y.astype(jnp.int32)[:, :1],
                                    (8, _TOY_TILE))

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[_toy_specs(8, _TOY_TILE)],
            out_specs=_toy_specs(8, _TOY_TILE),
            out_shape=jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),
        )(x)

    args = (jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),)
    # Sigma|products| = 128 * 100^2 well below 2^24: the ONLY defect is
    # the missing precision keyword.
    return fn, args, {0: (0, 100)}


def _build_f32_accum_overflow():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        xf = x_ref[:].astype(jnp.float32)
        # HIGHEST precision, every product exact (512^2 = 2^18), but the
        # accumulated sum 128 * 2^18 = 2^25 exceeds the f32 mantissa.
        y = jax.lax.dot_general(xf, xf, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST)
        o_ref[:] = jnp.broadcast_to(y.astype(jnp.int32)[:, :1],
                                    (8, _TOY_TILE))

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[_toy_specs(8, _TOY_TILE)],
            out_specs=_toy_specs(8, _TOY_TILE),
            out_shape=jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),
        )(x)

    args = (jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),)
    return fn, args, {0: (0, 1 << 9)}


def _build_f32_unvetted_roundtrip():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        xf = x_ref[:].astype(jnp.float32)
        # integer_pow is on the determinism allowlist but has no vetted
        # exact-float transfer: the certificate must demote here and the
        # astype(int32) round-trip must fail with a sourced diagnostic.
        y = xf ** 2
        o_ref[:] = y.astype(jnp.int32)

    def fn(x):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[_toy_specs(8, _TOY_TILE)],
            out_specs=_toy_specs(8, _TOY_TILE),
            out_shape=jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),
        )(x)

    args = (jax.ShapeDtypeStruct((8, 2 * _TOY_TILE), jnp.int32),)
    return fn, args, {0: (0, 100)}


NEGATIVES = {
    "oob-index-map": _build_oob_index_map,
    "read-before-write": _build_read_before_write,
    "mul-overflow-no-canon": _build_mul_overflow,
    "double-write": _build_double_write,
    "f32-default-precision-dot": _build_f32_default_precision_dot,
    "f32-accum-overflow": _build_f32_accum_overflow,
    "f32-unvetted-roundtrip": _build_f32_unvetted_roundtrip,
}


def analyze_negative(name: str) -> "IV.Report":
    """Analyze one deliberately broken toy kernel; the report must come
    back not-ok or the gate is dead."""
    fn, args, in_bounds = NEGATIVES[name]()
    return IV.analyze(fn, args, f"pallas.negative.{name}",
                      in_bounds=in_bounds)


def analyze_positive_toy() -> "IV.Report":
    """A minimal clean Pallas kernel: proves the machinery end to end
    without paying for the real verify kernel."""
    fn, args, in_bounds = _build_positive()
    return IV.analyze(fn, args, "pallas.toy", in_bounds=in_bounds)
