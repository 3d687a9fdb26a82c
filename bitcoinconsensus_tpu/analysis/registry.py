"""Registry of consensus kernels the static analyzer must prove.

Every traced program whose output feeds a consensus verdict is listed
here with the input bounds it is entitled to assume (the same contracts
`ops/limbs.py` documents: W2 weak-representation rows for field inputs,
canonical rows for unpacked coordinates, small windows for digits) and
the output bounds it promises (checked against the analyzer's derived
intervals — `out_within` failing means the hand bookkeeping understates
reality, which is a release blocker, not an analyzer bug).

To register a new kernel:

    KERNELS.append(KernelSpec(
        name="my_kernel",
        build=lambda B: (my_fn, (arg_specs...,)),
        in_bounds={0: w2_rows(), ...},   # flat arg index -> bounds
        out_within=[w2_rows(), ...],     # or None per output
        heavy=False,                     # True: skipped by --quick / tests
    ))

and `scripts/consensus_lint.py` picks it up on the next run. Bounds are
(lo, hi) tuples, or a per-axis-0-row list of them; None means the full
lane range of the dtype.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import limbs as L
from ..ops import curve as C
from ..ops import sha256 as SH
from . import interval


DEFAULT_BATCH = 2  # two lanes: keeps batch-axis structure without cost


def w2_rows() -> List[Tuple[int, int]]:
    """Weak-representation input contract: per-limb [0, W2[i]]."""
    return [(0, int(b)) for b in L.W2]


def canon_rows() -> List[Tuple[int, int]]:
    """Canonical field element: every limb in [0, MASK]."""
    return [(0, L.MASK)] * L.NLIMB


@dataclass
class KernelSpec:
    name: str
    build: Callable  # B -> (fn, arg_specs)
    in_bounds: Optional[Dict[int, object]] = None
    out_within: Optional[Sequence[object]] = None
    heavy: bool = False
    note: str = ""

    def analyze(self, batch: int = DEFAULT_BATCH) -> "interval.Report":
        fn, args = self.build(batch)
        return interval.analyze(
            fn, args, self.name,
            in_bounds=self.in_bounds, out_within=self.out_within,
        )


def _fe(B):
    return jax.ShapeDtypeStruct((L.NLIMB, B), jnp.int32)


def _flags(B):
    return jax.ShapeDtypeStruct((B,), jnp.int32)


def _bools(B):
    return jax.ShapeDtypeStruct((B,), jnp.bool_)


def _u8(B, n):
    return jax.ShapeDtypeStruct((B, n), jnp.uint8)


_W2 = None  # built lazily so importing this module stays cheap


def _specs() -> List[KernelSpec]:
    w2 = w2_rows()
    canon = canon_rows()
    fe3 = [w2, w2, w2, None]
    specs = [
        KernelSpec(
            "limbs.fe_add", lambda B: (L.fe_add, (_fe(B), _fe(B))),
            in_bounds={0: w2, 1: w2}, out_within=[w2],
        ),
        KernelSpec(
            "limbs.fe_sub", lambda B: (L.fe_sub, (_fe(B), _fe(B))),
            in_bounds={0: w2, 1: w2}, out_within=[w2],
        ),
        KernelSpec(
            "limbs.fe_mul", lambda B: (L.fe_mul, (_fe(B), _fe(B))),
            in_bounds={0: w2, 1: w2}, out_within=[w2],
            note="Karatsuba; transient int32 wraps are expected and legal",
        ),
        KernelSpec(
            "limbs.fe_sqr", lambda B: (L.fe_sqr, (_fe(B),)),
            in_bounds={0: w2}, out_within=[w2],
        ),
        KernelSpec(
            "mxu.fe_mul_onehot",
            lambda B: (_mxu_mul_fn(), (_fe(B), _fe(B))),
            in_bounds={0: w2, 1: w2}, out_within=[w2],
            note="MXU one-hot fe_mul candidate: every f32 value carries "
                 "an exactness certificate (accumulated Sigma|products| "
                 "<= 2^24 at Precision.HIGHEST); see ops/mxu_mul.py",
        ),
        KernelSpec(
            "limbs.fe_canon", lambda B: (L.fe_canon, (_fe(B),)),
            in_bounds={0: w2}, out_within=[canon],
        ),
        KernelSpec(
            "limbs.fe_is_zero", lambda B: (L.fe_is_zero, (_fe(B),)),
            in_bounds={0: w2},
        ),
        KernelSpec(
            "limbs.fe_inv", lambda B: (L.fe_inv, (_fe(B),)),
            in_bounds={0: w2}, out_within=[w2],
        ),
        KernelSpec(
            "curve.jacobian_double",
            lambda B: (C.jacobian_double, (_fe(B),) * 3),
            in_bounds={0: w2, 1: w2, 2: w2}, out_within=[w2, w2, w2],
        ),
        KernelSpec(
            "curve.jacobian_add_complete",
            lambda B: (C.jacobian_add_complete, (_fe(B),) * 6 + (_bools(B),) * 2),
            in_bounds={i: w2 for i in range(6)}, out_within=fe3,
        ),
        KernelSpec(
            "curve.jacobian_madd_complete",
            lambda B: (C.jacobian_madd_complete,
                       (_fe(B),) * 5 + (_bools(B),)),
            in_bounds={i: w2 for i in range(5)}, out_within=fe3,
        ),
        KernelSpec(
            "sha256.compress",
            lambda B: (SH.sha256_compress,
                       (jax.ShapeDtypeStruct((8, B), jnp.uint32),
                        jax.ShapeDtypeStruct((16, B), jnp.uint32))),
            note="uint32 wrap-by-spec: every op is a residue function",
        ),
        KernelSpec(
            "sha256.bip340_challenge",
            lambda B: (SH.bip340_challenge,
                       (_u8(B, 32), _u8(B, 32), _u8(B, 32))),
        ),
        KernelSpec(
            "curve.double_scalar_mult_glv",
            lambda B: (C.double_scalar_mult_glv,
                       (_fe(B),
                        jax.ShapeDtypeStruct((32, B), jnp.int32),
                        jax.ShapeDtypeStruct((32, B), jnp.int32),
                        _bools(B), _bools(B), _fe(B), _fe(B))),
            in_bounds={0: canon, 1: (0, 15), 2: (0, 15),
                       5: canon, 6: canon},
            out_within=fe3,
            heavy=True,
            note="GLV ladder: scan fixpoint over 32 windows + f32 MXU "
                 "G-table select",
        ),
        KernelSpec(
            "jax_backend.verify_kernel",
            lambda B: (_verify_kernel_fn(),
                       (jax.ShapeDtypeStruct((B, 4, 32), jnp.uint8),
                        _flags(B), _flags(B), _flags(B), _flags(B),
                        _flags(B), _bools(B))),
            in_bounds={1: (0, 1), 2: (-1, 1), 3: (0, 1), 4: (0, 1),
                       5: (0, 1)},
            out_within=[[(0, 1)] * DEFAULT_BATCH],
            heavy=True,
            note="the full device-side verify batch (~70k eqns)",
        ),
        KernelSpec(
            "jax_backend.verdict_checksum",
            lambda B: (_verdict_checksum_fn(), (_bools(B),)),
            in_bounds={0: (0, 1)},
            # count sum <= B; weighted sum <= B * (max lane weight 251)
            out_within=[[(0, DEFAULT_BATCH)], [(0, DEFAULT_BATCH * 251)]],
            note="in-flight verdict checksum: any single-lane flip moves "
                 "the count sum, any count-preserving swap moves the "
                 "weighted sum (settle seam recomputes both on host)",
        ),
        KernelSpec(
            "pallas.verify_tiles",
            lambda B: _pallas_verify_build(),
            # Flag contract single-sourced from ops/pallas_kernel.py
            # (same shape as jax_backend.verify_kernel's); the limb
            # contracts live below the byte-unpack preamble and are
            # re-derived, not assumed.
            in_bounds=_pallas_flag_bounds(),
            # Two (B,) verdict vectors, each lane provably 0/1 — the same
            # pin the XLA verify kernel carries, independently re-derived
            # through the Mosaic kernel's Ref semantics.
            out_within=[[(0, 1)] * _PALLAS_B] * 2,
            heavy=True,
            note="the fused Mosaic kernel: Ref-semantics interval proof + "
                 "grid/BlockSpec + VMEM budget (analysis/pallas_check.py)",
        ),
    ]
    return specs


def _mxu_mul_fn():
    from ..ops import mxu_mul as M
    return M.fe_mul_onehot


def _verify_kernel_fn():
    from ..crypto import jax_backend as JB
    return JB._verify_kernel


def _verdict_checksum_fn():
    from ..crypto import jax_backend as JB
    return JB._verdict_checksum


# verify_tiles requires B % LANE_TILE == 0 and a multi-step grid is the
# interesting case, so the Pallas spec ignores the requested batch and
# proves two grid steps of the dense tile (8 sublane rows of 128 lanes).
_PALLAS_B = 2048


def _pallas_flag_bounds():
    from ..ops import pallas_kernel as PK
    return dict(PK.FLAG_BOUNDS)


def _pallas_verify_build():
    from . import pallas_check  # noqa: F401  registers the Ref rules
    from ..ops import pallas_kernel as PK

    assert PK.tile_grid(_PALLAS_B) == (8, PK.VREG_LANES, 2)
    B = _PALLAS_B

    def fn(fields, want_odd, parity_req, has_t2, neg1, neg2, valid):
        return PK.verify_tiles(fields, want_odd, parity_req, has_t2,
                               neg1, neg2, valid)

    return fn, (jax.ShapeDtypeStruct((B, 4, 32), jnp.uint8),
                _flags(B), _flags(B), _flags(B), _flags(B),
                _flags(B), _bools(B))


@dataclass
class ScheduleSpec:
    """A scalar-schedule prover target (analysis/scalar_check.py): digit
    recoders, the GLV split, and the window ladders.  `certify` returns a
    CertResult whose status is THEOREM / VACUOUS / FAIL — fail-closed, the
    same discipline as the interval kernels above."""

    name: str
    heavy: bool = False  # heavy: eager ledger walk (~1-2 min on CPU)
    note: str = ""

    def certify(self, quick: bool = False):
        from . import scalar_check
        return scalar_check.certify(self.name, quick=quick)


def _schedule_specs() -> List[ScheduleSpec]:
    return [
        ScheduleSpec("scalar._digits",
                     note="4-bit window recoding: exact bit-slice theorem"),
        ScheduleSpec("scalar._digits128",
                     note="4-bit recoding of GLV halves + congruence planes"),
        ScheduleSpec("scalar.bytes_to_limbs",
                     note="byte->limb packing, 32B/20L and 16B/10L"),
        ScheduleSpec("sha256.bytes_from_words",
                     note="digest word->byte unpack, big-endian slices"),
        ScheduleSpec("scalar._signed_digits128",
                     note="signed window recoder: exhaustive carry automaton"),
        ScheduleSpec("glv.split_lambda",
                     note="lattice constants + |k1|,|k2| < 2^128 certificate"),
        ScheduleSpec("curve.double_scalar_mult_glv", heavy=True,
                     note="GLV ladder weight ledger + differential"),
        ScheduleSpec("pallas.kernel_schedule", heavy=True,
                     note="Mosaic kernel: table object-flow + signed ledger"),
    ]


def all_schedules(include_heavy: bool = True) -> List[ScheduleSpec]:
    specs = _schedule_specs()
    if not include_heavy:
        specs = [s for s in specs if not s.heavy]
    return specs


def get_schedule(name: str) -> ScheduleSpec:
    for s in _schedule_specs():
        if s.name == name:
            return s
    raise KeyError(name)


def all_kernels(include_heavy: bool = True) -> List[KernelSpec]:
    specs = _specs()
    if not include_heavy:
        specs = [s for s in specs if not s.heavy]
    return specs


def get_kernel(name: str) -> KernelSpec:
    for s in _specs():
        if s.name == name:
            return s
    raise KeyError(name)
