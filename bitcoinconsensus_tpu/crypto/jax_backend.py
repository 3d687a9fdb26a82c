"""Batched TPU signature verification: one kernel for ECDSA, Schnorr, taproot.

The reference verifies one signature per call on one core
(`secp256k1_ecdsa_verify`, `secp256k1/src/secp256k1.c:423`;
`secp256k1_schnorrsig_verify`, `modules/schnorrsig/main_impl.h:190`;
`secp256k1_xonly_pubkey_tweak_add_check`, `modules/extrakeys/main_impl.h:109`).
All three reduce to the same algebra — compute R = a·G + b·P and compare R
against a target — so this backend folds a *mixed* batch of all three check
kinds into ONE device program over `double_scalar_mult_glv`:

    kind      a        b      P            accept
    ECDSA     m/s      r/s    pubkey       R.x ∈ {r, r+n} (mod p)
    Schnorr   s        n-e    lift_x(pk)   R.x == r and even(R.y)
    tweak     t        1      lift_x(pki)  R.x == out_x and parity matches

Three choices shape the host/device boundary:

- **Byte-packed transfers**: each check ships as 4 x 32-byte fields
  (a, GLV-split |b1|‖|b2|, pubkey-x, target) + 6 flag bytes — 135 B/lane
  instead of ~500 B of pre-split limbs. Limb splitting, window-digit
  extraction, y-lifting (fe_sqrt), and the r+n secondary target all
  happen on device.
- **One piece each way**: a launch costs the host by the piece, not by
  the byte, so a dispatch is ONE packed buffer put (`crypto/lane_wire.py`,
  the mesh verifier's format too), ONE device program (unpack, the
  kernel, the verdict checksum) and ONE int32 result whose host copy is
  asked for at launch, so that the settle finds the bytes on the host.
- **Pipelined chunk dispatch**: large batches go out in chunks whose
  transfers/compute overlap the host-side prep of the next chunk (JAX
  async dispatch); the per-roundtrip sync cost is paid once.

Host-side prep (byte parsing, lax-DER, batched modular inverse of s, BIP340
challenge hashes) is branchy and tiny; device-side is the uniform 256-bit
double-and-add — the split the SURVEY §7 architecture prescribes. Lanes
that fail host-side structural checks get dummy field values and a False
mask. Batches are padded to the next power of two (>= min_batch) so jit
caches a handful of shapes. Results are bit-identical to the host oracle
(`crypto/secp_host.py`), which is itself differentially tested against the
consensus vectors.
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import counter as _obs_counter
from ..obs import gauge as _obs_gauge
from ..obs import monotonic as _monotonic
from ..utils import compile_cache as _compile_cache
from ..utils.hashes import tagged_hash
from ..utils.gcpause import gc_paused
from ..utils.profiling import Phases
from ..ops.limbs import (
    MASK,
    NLIMB,
    P_INT,
    bytes_to_limbs,
    fe_add,
    fe_canon,
    fe_is_zero,
    fe_mul,
    fe_sqr,
    fe_sqrt,
    fe_sub,
    int_to_limbs,
)
from ..ops.curve import (
    G_X,
    G_Y,
    _GX_LIMBS,
    _GY_LIMBS,
    _digits128,
    double_scalar_mult_glv,
    jacobian_to_affine,
)
from ..ops.regions import named_region, region_scope
from .glv import split_lambda
from .secp_host import N, parse_der_lax
from ..resilience import degrade as _degrade
from ..resilience import faults as _faults
from ..resilience import guards as _guards
from ..resilience import inflight as _inflight
from . import lane_wire as _wire

__all__ = ["SigCheck", "TpuSecpVerifier", "default_verifier"]

_CONFIG_ERRORS = _obs_counter(
    "consensus_backend_config_errors_total",
    "backend/config setup steps that failed and were skipped",
    ("step",),
)

# Persistent XLA compilation cache: the verify kernel is a large traced
# program; caching makes every process after the first fast. Placement
# rule (environment variable, else one fixed in-checkout path) lives in
# utils/compile_cache.py.
_compile_cache.configure()

# Device-dispatch telemetry (README "Observability"). All host-side: these
# run in the driver around `jit` calls, never inside a traced program, so
# the analysis determinism gate sees identical kernel jaxprs.
# Fed here by `verify_checks_begin` and, on the index path, by
# models/batch.py `IdxFixpoint.finish` (the lanes a fixpoint sent).
_CHECKS_TOTAL = _obs_counter(
    "consensus_checks_total", "deferred curve checks by kind", ("kind",)
)
_DISPATCH_TOTAL = _obs_counter(
    "consensus_dispatch_total", "device dispatches by backend", ("backend",)
)
_DISPATCH_TILES = _obs_counter(
    "consensus_dispatch_tiles_total",
    "grid steps of the Pallas programs dispatched, by the sublane rows a "
    "step's tile fills: 8 is the dense tile, 4 the half-filled one of the "
    "512-lane shape",
    ("rows",),
)
_DISPATCH_LANES = _obs_counter(
    "consensus_dispatch_lanes_total", "real (unpadded) lanes dispatched"
)
_DISPATCH_PADDED = _obs_counter(
    "consensus_dispatch_padded_lanes_total",
    "padded lanes dispatched (pad ladder fill)",
)
_DISPATCH_FILL = _obs_gauge(
    "consensus_dispatch_fill_ratio",
    "real/padded lane ratio of the most recent dispatch",
)
_NEW_SHAPES = _obs_counter(
    "consensus_dispatch_new_shapes_total",
    "distinct padded dispatch shapes this process (each is one jit "
    "compile or persistent-cache load)",
)
_TRANSFERS = _obs_counter(
    "consensus_dispatch_transfers_total",
    "host-device transfers of one-device dispatches, a count a piece, by "
    "direction: in = arguments put, out = results' host copies asked for "
    "(a packed dispatch makes one each way)",
    ("dir",),
)
_HOST_FIXUPS = _obs_counter(
    "consensus_host_fixup_total",
    "exceptional device lanes resolved exactly on host",
)
_LAUNCH_SECONDS = _obs_gauge(
    "consensus_dispatch_launch_seconds",
    "host seconds one kernel launch call took, by backend and padded "
    "shape: `first` is the shape's first dispatch by this verifier (trace "
    "+ jit compile or persistent-cache load + enqueue), `warm` the latest "
    "later one (enqueue only)",
    ("backend", "padded", "which"),
)


class SigCheck:
    """One deferred signature-algebra check (host-parsed, device-verified).

    kind: 'ecdsa'   -> data = (pubkey_bytes, sig_der_no_hashtype, msg32)
          'schnorr' -> data = (pubkey32, sig64, msg32)
          'tweak'   -> data = (tweaked32, parity, internal32, tweak32)
    """

    __slots__ = ("kind", "data")

    def __init__(self, kind: str, data: Tuple):
        assert kind in ("ecdsa", "schnorr", "tweak")
        self.kind = kind
        self.data = data


def _batch_inv_mod_n(vals: List[int]) -> List[int]:
    """Montgomery batch inversion mod the group order n (one modexp total)."""
    prefix = []
    acc = 1
    for v in vals:
        acc = acc * v % N
        prefix.append(acc)
    inv = pow(acc, N - 2, N)
    out = [0] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = inv * (prefix[i - 1] if i else 1) % N
        inv = inv * vals[i] % N
    return out


class _Lane:
    """Host-parsed check, ready for byte packing.

    a: fixed-base scalar (< n); the variable-base scalar b ships GLV-split
    as (|b1|, |b2|, neg1, neg2) with |bi| < 2^128 (`crypto/glv.py` —
    halves the doubling count on device). px: the point's x coordinate;
    want_odd: parity of the y lift (valid pubkeys always resolve to a
    parity — uncompressed keys are curve-checked on host, so y is
    recomputable from its parity); t1: the x-coordinate target; has_t2
    marks the ECDSA r+n secondary target (only when r + n < p);
    parity_req constrains R.y parity (-1 don't care / 0 even / 1 odd).
    """

    __slots__ = (
        "valid", "a", "b1", "b2", "neg1", "neg2", "px", "want_odd", "t1",
        "has_t2", "parity",
    )

    def __init__(self):
        self.valid = False
        self.a = 0
        self.b1 = 0
        self.b2 = 0
        self.neg1 = 0
        self.neg2 = 0
        self.px = G_X
        self.want_odd = 0
        self.t1 = 0
        self.has_t2 = 0
        self.parity = -1

    def set_b(self, b: int) -> None:
        self.b1, self.neg1, self.b2, self.neg2 = split_lambda(b)


def _host_parse_pubkey(lane: _Lane, pubkey: bytes) -> bool:
    """Structural half of secp256k1_ec_pubkey_parse (eckey_impl.h): length,
    prefix, range and (for uncompressed forms) on-curve/hybrid checks. The
    expensive decompression square root runs on device (fe_sqrt)."""
    if len(pubkey) == 33 and pubkey[0] in (2, 3):
        x = int.from_bytes(pubkey[1:], "big")
        if x >= P_INT:
            return False
        lane.px = x
        lane.want_odd = 1 if pubkey[0] == 3 else 0
        return True
    if len(pubkey) == 65 and pubkey[0] in (4, 6, 7):
        x = int.from_bytes(pubkey[1:33], "big")
        y = int.from_bytes(pubkey[33:], "big")
        if x >= P_INT or y >= P_INT:
            return False
        if (y * y - (x * x % P_INT * x + 7)) % P_INT != 0:
            return False
        if pubkey[0] == 6 and (y & 1):
            return False
        if pubkey[0] == 7 and not (y & 1):
            return False
        # y is on-curve, hence exactly the lift of its own parity: the
        # device recomputes it from (x, want_odd) — y itself never ships.
        lane.px, lane.want_odd = x, y & 1
        return True
    return False


def _prep_ecdsa(lane: _Lane, pubkey: bytes, sig_der: bytes, msg32: bytes):
    """Mirror of CPubKey::Verify host half (pubkey.cpp:191-207): parse
    pubkey, lax-DER parse, normalize S; u1/u2 are filled in later after the
    batched inversion. Returns (r, s, m) for the inversion batch, or None."""
    if not _host_parse_pubkey(lane, pubkey):
        return None
    rs = parse_der_lax(sig_der)
    if rs is None:
        return None
    r, s = rs
    if s > N // 2:
        s = N - s  # normalize high-S (pubkey.cpp:204)
    if r == 0 or s == 0:
        return None
    lane.t1 = r
    lane.has_t2 = 1 if r + N < P_INT else 0
    lane.valid = True
    return r, s, int.from_bytes(msg32, "big") % N


def _prep_schnorr(
    lane: _Lane, pubkey32: bytes, sig64: bytes, msg32: bytes,
    defer_challenge: bool = False,
):
    """BIP340 verify host half (modules/schnorrsig/main_impl.h:190-237).

    With `defer_challenge` the structural work happens here but the
    challenge hash is left to the caller (returns the (r32, px32, m32)
    triple to feed `ops/sha256.bip340_challenge` in one device batch;
    caller must then `lane.set_b((N - e) % N)`)."""
    if len(pubkey32) != 32 or len(sig64) != 64:
        return None
    px = int.from_bytes(pubkey32, "big")
    if px >= P_INT:
        return None
    r = int.from_bytes(sig64[:32], "big")
    s = int.from_bytes(sig64[32:], "big")
    if r >= P_INT or s >= N:
        return None
    lane.px = px
    lane.want_odd = 0  # BIP340 lift_x: even y; device checks existence
    lane.a = s
    lane.t1 = r
    lane.parity = 0  # require even R.y
    lane.valid = True
    if defer_challenge:
        return (sig64[:32], pubkey32, msg32)
    e = int.from_bytes(
        tagged_hash("BIP0340/challenge", sig64[:32] + pubkey32 + msg32), "big"
    ) % N
    lane.set_b((N - e) % N)  # (n-e)·P = -e·P
    return None


def _prep_tweak(lane: _Lane, tweaked32: bytes, parity: int, internal32: bytes,
                tweak32: bytes):
    """Taproot commitment check host half (extrakeys/main_impl.h:109-129):
    Q = P_internal + t·G must equal (tweaked_x, parity)."""
    px = int.from_bytes(internal32, "big")
    if px >= P_INT:
        return
    t = int.from_bytes(tweak32, "big")
    if t >= N:
        return
    tx = int.from_bytes(tweaked32, "big")
    lane.px = px
    lane.want_odd = 0  # x-only internal key: even-y lift, device-checked
    lane.a = t
    lane.set_b(1)
    # tx >= p can never equal a canonical x coordinate; the raw compare
    # below is False for such lanes with no sentinel machinery.
    lane.t1 = tx
    lane.parity = parity & 1
    lane.valid = True


_SEVEN_LIMBS = int_to_limbs(7)
_N_LIMBS = int_to_limbs(N)


def _verify_kernel(fields, want_odd, parity_req, has_t2, neg1, neg2, valid):
    """Device side of the mixed verify batch.

    fields: (B, 4, 32) uint8 — little-endian (a, |b1|‖|b2|, px, t1) per
    lane (the variable-base scalar arrives GLV-split: two 16-byte halves
    sharing field 1, signs in neg1/neg2). Unpacks to limb-major (20, B),
    lifts P's y from (px, want_odd) via fe_sqrt, runs
    R = a·G + (±b1 ± lambda·b2)·P with the GLV schedule, and accepts per
    lane: R.x == t1, or (has_t2) R.x == t1 + n, with optional R.y parity.

    Region scopes (`ops/regions.py`) split the program for device-time
    attribution: point_decode (unpack + y-lift + sanitize), scalar_mult
    (the GLV ladder, via its own decorator), verdict (affine + compare).
    They add zero ops — the provers see an identical jaxpr."""
    with region_scope("point_decode"):
        a = bytes_to_limbs(fields[:, 0])
        b1 = bytes_to_limbs(fields[:, 1, :16], nlimb=10)
        b2 = bytes_to_limbs(fields[:, 1, 16:], nlimb=10)
        px = bytes_to_limbs(fields[:, 2])
        t1 = bytes_to_limbs(fields[:, 3])

        seven = jnp.broadcast_to(
            jnp.asarray(_SEVEN_LIMBS).reshape(NLIMB, 1), px.shape
        ).astype(px.dtype)
        rhs = fe_add(fe_mul(fe_sqr(px), px), seven)  # x^3 + 7
        ycand = fe_canon(fe_sqrt(rhs))
        sq_ok = fe_is_zero(fe_sub(fe_mul(ycand, ycand), rhs))
        odd = (ycand[0] & 1) == 1
        yneg = fe_sub(jnp.zeros_like(ycand), ycand)  # weak rep is fine here
        flip = odd != (want_odd == 1)
        py = jnp.where(flip[None], yneg, ycand)
        valid = valid & sq_ok
        # Sanitize: invalid lanes (non-residue x — off-curve garbage) are
        # replaced by the generator so EVERY lane runs on-curve group math.
        # This keeps the explicitly-tracked infinity masks sound (off-curve
        # orbits obey no group law and could hit Z ≡ 0 unflagged, which
        # would zero the cross-lane batch-inversion product); the verdicts
        # of these lanes are masked by `valid` regardless.
        gxb = jnp.broadcast_to(
            jnp.asarray(_GX_LIMBS).reshape(NLIMB, 1), px.shape
        ).astype(px.dtype)
        gyb = jnp.broadcast_to(
            jnp.asarray(_GY_LIMBS).reshape(NLIMB, 1), px.shape
        ).astype(px.dtype)
        px = jnp.where(valid[None], px, gxb)
        py = jnp.where(valid[None], py, gyb)

    X, Y, Z, r_inf = double_scalar_mult_glv(
        a, _digits128(b1), _digits128(b2), neg1 == 1, neg2 == 1, px, py
    )

    with region_scope("verdict"):
        x, y, inf = jacobian_to_affine(X, Y, Z, inf=r_inf)

        nl = jnp.broadcast_to(
            jnp.asarray(_N_LIMBS).reshape(NLIMB, 1), t1.shape
        ).astype(t1.dtype)
        t1n = fe_canon(t1 + nl, bounds=[2 * MASK] * NLIMB)  # r+n (< p)
        ok_x = jnp.all(x == t1, axis=0) | (
            (has_t2 == 1) & jnp.all(x == t1n, axis=0)
        )
        y_odd = (y[0] & 1) == 1
        par_ok = (parity_req < 0) | (y_odd == (parity_req == 1))
        return valid & ~inf & ok_x & par_ok


@named_region("verdict_checksum")
def _verdict_checksum(ok):
    """Device-side verdict checksum: (count, position-weighted) int32 sums.

    Computed over the kernel's pristine `ok` inside the dispatch's one
    program (`_packed_program`; a shard at a time in the mesh's), behind
    the proven verify kernels, which are untouched; the settle seam
    recomputes both sums host-side from the materialized buffer and any
    mismatch (a single-lane flip anywhere, a replayed buffer) demotes the
    ticket to the host oracle. Weights are i % 251 + 1, keeping the
    weighted sum < 252·B — int32-safe to ~8.5M lanes (registered with the
    interval prover as `jax_backend.verdict_checksum`).
    """
    v = ok.astype(jnp.int32)
    w = jnp.arange(v.shape[0], dtype=jnp.int32) % jnp.int32(
        _guards.CHECKSUM_MOD
    ) + jnp.int32(1)
    return jnp.sum(v), jnp.sum(v * w)


@functools.lru_cache(maxsize=None)
def _packed_program(backend: str):
    """The ONE device program of a one-device dispatch on `backend`
    ("pallas" or "xla"): `packed uint8[padded, ROW_BYTES] ->
    int32[padded + 2]`. Its first ops slice and widen the packed rows to
    the kernel's seven arguments (`lane_wire`), then the kernel, then
    `_verdict_checksum` over its pristine `ok`; the result is `ok + 2 *
    needs_host` a row and the checksum pair as the tail (the complete-add
    XLA kernel defers no lane)."""

    def program(packed):
        *lanes, _live = _wire._unpack_lanes_traced(packed)
        if backend == "pallas":
            # Deferred import keeps CPU-only paths light. The kernel's own
            # function, not its jit: a jit nested in this one read 80-90 s
            # more tracing and lowering a shape on the chip's host
            # (PERF.md section 6, PR 43).
            from ..ops.pallas_kernel import verify_tiles

            ok, needs = verify_tiles.__wrapped__(*lanes)
        else:
            ok = _verify_kernel(*lanes)
            needs = jnp.zeros_like(ok)
        return _wire.pack_result_traced(ok, needs, list(_verdict_checksum(ok)))

    # The program's name in a profiler trace (`XLA Modules`: `jit_<name>`),
    # after the kernel inside it, as the mesh's `mesh_verify_tiles` is.
    program.__name__ = program.__qualname__ = (
        "packed_verify_tiles" if backend == "pallas" else "packed__verify_kernel"
    )
    return jax.jit(program)


class TpuSecpVerifier:
    """Batched verifier; pads to power-of-two batch shapes and jits once per
    shape (persistent XLA cache across processes). Large batches are split
    into `chunk` -lane dispatches pipelined back-to-back.

    Two device backends, bit-identical results (tests/test_pallas_kernel.py):
    - XLA-traced kernel (`_verify_kernel`) — every platform; the only
      choice for small batches and the CPU mesh tests.
    - Pallas mega-kernel (`ops/pallas_kernel.verify_tiles`) — TPU batches
      of >= LANE_TILE lanes; the whole scalar-mult pipeline VMEM-resident.
    Selection is automatic (TPU + large batch); BITCOINCONSENSUS_TPU_PALLAS
    =0/1 forces it off/on.
    """

    def __init__(
        self,
        min_batch: int = 8,
        chunk: int = 1 << 13,
        pad_step: Optional[int] = None,
        device_challenge: Optional[bool] = None,
    ):
        """`pad_step`: cap the power-of-two pad ladder at the next multiple
        of this step (small batches still pad to the ladder). Every distinct
        padded shape compiles once (15-60 s for the pallas kernel), so a
        small step only pays off for a recurring batch size — e.g. a
        block-replay driver padding ~5.6k checks to 6144 (step 2048)
        instead of 8192 saves ~25% device time after the one-time compile.
        Must be a multiple of the 512-lane pallas tile (and min_batch a
        power of two times 512) or TPU dispatches silently fall back to the
        slower XLA kernel."""
        if pad_step is not None and (pad_step <= 0 or pad_step % 512 != 0):
            raise ValueError(
                "pad_step must be a positive multiple of the 512-lane tile"
            )
        # BIP340 challenges via the batched device SHA-256 (ops/sha256) in
        # the Python prep path; the native C++ prep hashes in-process (the
        # same midstate trick at memory speed), so this only matters when
        # the native core is absent.
        if device_challenge is None:
            device_challenge = os.environ.get(
                "BITCOINCONSENSUS_TPU_DEVICE_SHA", ""
            ) in ("1", "on")
        self._device_challenge = bool(device_challenge)
        self._min_batch = min_batch
        self._chunk = chunk
        self._pad_step = pad_step
        env = os.environ.get("BITCOINCONSENSUS_TPU_PALLAS", "")
        if env in ("0", "off"):
            self._use_pallas = False
        elif env in ("1", "on"):
            self._use_pallas = True
        else:
            try:
                self._use_pallas = jax.default_backend() == "tpu"
            except RuntimeError:  # pragma: no cover - no usable backend
                # Every launch will fail the same way and the ladder will
                # land on the host rung; the cause stays in the telemetry.
                _CONFIG_ERRORS.inc(step="default_backend")
                self._use_pallas = False
        # Native host core (SURVEY §7): lane prep + packing in one C call,
        # ~10x the Python packers. Bit-identical output (tests/test_native.py);
        # the Python path stays as spec and fallback.
        from .. import native_bridge

        self._native = native_bridge if native_bridge.available() else None
        # Set when a deferred exceptional-case lane (pallas fast-add flag)
        # resolved FALSE on the host — consumed by the sharded verdict.
        self._fixup_failed = False
        # Padded shapes this instance has dispatched: first sight of a
        # shape means one jit compile (or persistent-cache load).
        self._seen_shapes: set = set()
        self.phases = Phases()  # host_prep / pack / backpressure / dispatch / sync (mesh: + shard_layout / shard_check)
        # Fault containment (resilience/): retry budget + backend
        # quarantine ladder. `_dispatch_level` is the rung the in-flight
        # dispatch runs at (set around each _run_packed call).
        self._resilience = _degrade.DispatchResilience(
            self._ladder_levels(), name=type(self).__name__
        )
        self._dispatch_level: Optional[str] = None
        # In-flight settlement queue (resilience/inflight.py): dispatch
        # returns tickets, settlement applies the guards/retry/ladder
        # policy. Depth bounds unsettled host state (backpressure);
        # deadline bounds how long a wedged ticket may retry before the
        # host oracle takes the lanes.
        self._inflight = _inflight.InflightQueue(
            self._resilience,
            self._SITE,
            launch=self._launch_ticket,
            materialize=self._materialize_guarded,
            prepare=self._prepare_ticket,
            on_device=self._on_device_settle,
            max_depth=int(os.environ.get(
                "BITCOINCONSENSUS_TPU_INFLIGHT_DEPTH", "4")),
            deadline_s=float(os.environ.get(
                "BITCOINCONSENSUS_TPU_SETTLE_DEADLINE_S", "8.0")),
        )

    @property
    def _resilience(self) -> _degrade.DispatchResilience:
        return self._resilience_obj

    @_resilience.setter
    def _resilience(self, value: _degrade.DispatchResilience) -> None:
        # Keep the in-flight queue on the same policy object: tests (and
        # operators) swap the resilience budget/ladder wholesale.
        self._resilience_obj = value
        queue = getattr(self, "_inflight", None)
        if queue is not None:
            queue._res = value

    def _pad(self, n: int) -> int:
        # `n + 1`, not `n`: every padded shape reserves at least one pad
        # lane for the rotating known-answer sentinel (containment floor).
        # Chunked drivers slice at `lane_capacity` (= chunk - 1) so full
        # chunks still land on the same power-of-two shape.
        size = self._min_batch
        while size < n + 1:
            size *= 2
        if self._pad_step is not None:
            # Whichever is smaller: the power-of-two ladder or the step
            # rounding — a 5.6k main dispatch pads to 6144 (not 8192) while
            # a 4-check oracle round still pads to min_batch, not a full step.
            step = self._pad_step
            return min(size, max(self._min_batch, ((n + step) // step) * step))
        return size

    def _prep_lanes(self, checks: Sequence[SigCheck]) -> List["_Lane"]:
        lanes = [_Lane() for _ in checks]
        ecdsa_pending = []  # (lane, r, s, m)
        schnorr_pending = []  # (lane, r32, px32, m32) — device-challenge mode
        for lane, chk in zip(lanes, checks, strict=True):
            if chk.kind == "ecdsa":
                got = _prep_ecdsa(lane, *chk.data)
                if got is not None:
                    ecdsa_pending.append((lane, *got))
            elif chk.kind == "schnorr":
                trip = _prep_schnorr(
                    lane, *chk.data, defer_challenge=self._device_challenge
                )
                if trip is not None:
                    schnorr_pending.append((lane, *trip))
            else:
                _prep_tweak(lane, *chk.data)
        if ecdsa_pending:
            sinvs = _batch_inv_mod_n([s for _, _, s, _ in ecdsa_pending])
            for (lane, r, _s, m), sinv in zip(ecdsa_pending, sinvs, strict=True):
                lane.a = m * sinv % N  # u1
                lane.set_b(r * sinv % N)  # u2
        if schnorr_pending:
            # ONE batched device dispatch for every BIP340 challenge
            # (ops/sha256 midstate path) instead of per-lane host hashing;
            # bit-identical (tests/test_ops_sha256.py) — the GLV split of
            # (n - e) still happens host-side where the wide-int math is.
            from ..ops.sha256 import bip340_challenge

            stack = np.stack(
                [
                    np.frombuffer(r + px + m, dtype=np.uint8)
                    for _, r, px, m in schnorr_pending
                ]
            )
            digests = _inflight.settle_array(
                bip340_challenge(stack[:, :32], stack[:, 32:64], stack[:, 64:])
            )
            for (lane, *_), d in zip(schnorr_pending, digests, strict=True):
                e = int.from_bytes(d.tobytes(), "big") % N
                lane.set_b((N - e) % N)  # (n-e)·P = -e·P
        return lanes

    def verify_checks(self, checks: Sequence[SigCheck]) -> np.ndarray:
        """Verify a mixed batch; returns bool array aligned with `checks`.

        Fully pipelined per chunk: while the device crunches chunk k, the
        host parses/packs chunk k+1 (JAX async dispatch); the roundtrip
        sync cost is paid once, at the end. Cycle collection is paused
        for the duration (utils/gcpause.py — full GC passes over the JAX
        heap otherwise dominate the host-side cost of large batches).
        Stream drivers split the two halves themselves
        (`verify_checks_begin` / `verify_checks_finish`) so host prep for
        batch N+1 overlaps batch N's wire time.
        """
        if not checks:
            return np.zeros(0, dtype=bool)
        with gc_paused():
            return self.verify_checks_finish(self.verify_checks_begin(checks))

    def verify_checks_begin(self, checks: Sequence[SigCheck]):
        """Async half of `verify_checks`: prep, pack and dispatch every
        chunk through the in-flight queue; returns a pending handle
        without synchronizing anything. The queue's bounded depth settles
        the oldest ticket first if a caller races too far ahead."""
        kinds: dict = {}
        for c in checks:
            kinds[c.kind] = kinds.get(c.kind, 0) + 1
        for k, cnt in kinds.items():
            _CHECKS_TOTAL.inc(cnt, kind=k)
        pending = []  # (ticket, start, count)
        cap = self.lane_capacity
        for start in range(0, len(checks), cap):
            sub_checks = checks[start : start + cap]
            if self._native is not None:
                with self.phases("host_prep"):
                    args = self._native.prep_pack(
                        sub_checks, self._pad(len(sub_checks))
                    )
            else:
                with self.phases("host_prep"):
                    sub = self._prep_lanes(sub_checks)
                with self.phases("pack"):
                    args = self._pack_lanes(sub)
            self._make_room()
            with self.phases("dispatch"):
                pending.append(
                    (self._dispatch_guarded(args, len(sub_checks)), start,
                     len(sub_checks))
                )
        return (checks, pending)

    def verify_checks_finish(self, handle) -> np.ndarray:
        """Settle a `verify_checks_begin` handle: every ticket resolves
        through the guards (or the host oracle) into the result array."""
        checks, pending = handle
        out = np.zeros(len(checks), dtype=bool)
        with self.phases("sync"):
            for ticket, start, count in pending:
                self._settle_guarded(ticket, checks, out, start, count)
        return out

    # --- fault containment (resilience/) --------------------------------
    #
    # Every dispatch flows through _dispatch_guarded (pick ladder rung,
    # seed sentinel lanes, catch dispatch-time faults) and settles through
    # _settle_device (validate the verdict buffer, retry within budget,
    # walk the quarantine ladder). A chunk no device rung could answer for
    # lands on the host-exact oracle — faults cost latency, never a wrong
    # ACCEPT, never a crash.

    _SITE = "jax_backend"

    def _ladder_levels(self) -> Tuple[str, ...]:
        if self._use_pallas:
            return ("pallas", "xla", _degrade.HOST_LEVEL)
        return ("xla", _degrade.HOST_LEVEL)

    def _run_level(self, packed: np.ndarray, n: int, level: str):
        self._dispatch_level = level
        try:
            return self._run_packed(packed, n)
        finally:
            self._dispatch_level = None

    def _pack_ticket(self, args: Tuple, n: int, padded: Optional[int] = None):
        """The kernel's seven arguments as one fresh packed buffer of
        `padded` rows (default: as many as `args` hold), the rotating
        known-answer lanes seeded into its pad region through the buffer's
        own views: `(packed, sentinel set)`. ONE pass out of buffers that
        may be read-only (the native arena's): no copy before this one."""
        packed = _wire.pack_lanes(args, n, padded)
        return packed, _guards.install_sentinels(
            _wire._lane_views(packed)[:-1], n
        )

    def _prepare_ticket(self, args: Tuple, n: int):
        """Dispatch-time prep (inflight queue callback): the chunk as
        `((packed,), sentinel set)` — every dispatch carries sentinels."""
        packed, sset = self._pack_ticket(args, n)
        return (packed,), sset

    def _launch_ticket(self, args: Tuple, n: int, level: str, sset=None):
        """Launch one packed chunk at `level` (inflight queue callback).
        `sset` is the prepare output (sentinel set; the sharded subclass
        passes its shard layout and routes on it). Returns (result, aux)
        with nothing synchronized; the checksum pair rides inside the one
        result, so there is no aux."""
        return self._run_level(args[0], n, level), None

    def _on_device_settle(self, ticket, ok, needs, all_ok) -> None:
        """Success hook (inflight queue callback): exactly once per
        cleanly settled ticket, so subclass verdict accounting can never
        double-count across retries."""
        self._note_device_verdict(all_ok, ok, needs, ticket.n)

    def _make_room(self) -> None:
        """The `backpressure` phase: with the in-flight queue at its depth
        limit, the wait for its oldest ticket(s) to settle, taken before
        the `dispatch` phase so that `dispatch` stays the launch alone. A
        round of more chunks than the queue is deep stands here, not at
        the settle seam (`sync`), for the kernels of its first chunks."""
        if self._inflight.full:
            with self.phases("backpressure"):
                self._inflight.make_room()

    def _dispatch_guarded(self, args: Tuple, n: int) -> _inflight.Ticket:
        """Async-dispatch one packed chunk; returns its in-flight ticket
        (unsynchronized device arrays + settle context + deadline)."""
        return self._inflight.dispatch(args, n)

    def _materialize_guarded(self, ticket: _inflight.Ticket):
        """The settle seam — the ONE place in-flight verdict buffers
        become host memory. Materialize + validate one ticket: structural
        guards, sentinel recheck, device-vs-host checksum compare.
        Returns (ok, needs, all_ok) — padded bool arrays and the sharded
        step's replicated verdict scalar (None off-mesh). Raises
        VerdictAnomaly on a buffer the guards reject."""
        padded = int(ticket.args[0].shape[0])
        ok, needs = self._settle_packed(ticket.result, padded, ticket.sset,
                                        seam=True)
        return ok, needs, None

    def _settle_packed(self, result, padded: int, sset, seam: bool = False):
        """One packed program's result through every guard: `(ok, needs)`
        padded bool arrays. ONE pull (the host copy was asked for at
        launch), the whole-buffer shape guard, then the guards in the order
        they have always had: the verdict domain of `ok` and of the
        deferral mask, the sentinels, the checksum. At the settle seam
        (`seam`) the `jax_backend.verdict` fault site sits on the unpacked
        `ok`, before any guard sees it."""
        raw = _inflight.settle_array(result)
        if raw.shape != (padded + _wire.CHECKSUM_TAIL,):
            _guards.GUARD_ANOMALIES.inc(site=self._SITE, reason="shape")
            raise _guards.VerdictAnomaly(
                self._SITE, "shape",
                f"got {raw.shape}, want ({padded + _wire.CHECKSUM_TAIL},)",
            )
        ok_np, needs_np, tail = _wire.split_result(raw, 1, _wire.CHECKSUM_TAIL)
        if seam:
            ok_np = _faults.corrupt_verdict("jax_backend.verdict", ok_np)
        ok = _guards.validate_verdict(ok_np, padded, self._SITE)
        needs = _guards.validate_verdict(needs_np, padded, self._SITE)
        _guards.check_sentinels(sset, ok, needs, self._SITE)
        # The device sums were computed over the pristine verdicts inside
        # the program; recomputing from the materialized (possibly
        # corrupted-in-transit) copy catches any single-lane flip —
        # real-lane region included.
        _guards.check_checksum(
            (int(tail[0, 0]), int(tail[0, 1])), ok, self._SITE
        )
        return ok, needs

    def _settle_device(self, ticket: _inflight.Ticket, count: int):
        """Settle one ticket through the in-flight queue's retry/
        degradation policy. Returns (ok, needs) padded arrays that passed
        every guard, or None when the chunk must resolve on the
        host-exact oracle (fail-closed terminal)."""
        return self._inflight.settle(ticket)

    def _settle_guarded(self, ticket: _inflight.Ticket,
                        checks: Sequence[SigCheck], out: np.ndarray,
                        start: int, count: int) -> None:
        settled = self._settle_device(ticket, count)
        if settled is None:
            host_res = np.fromiter(
                (self._host_check(checks[start + i]) for i in range(count)),
                dtype=bool, count=count,
            )
            out[start : start + count] = host_res
            self._note_host_lanes(host_res)
            return
        ok, needs = settled
        out[start : start + count] = ok[:count]
        if needs is not None:
            needs_np = needs[:count]
            if needs_np.any():
                # Exceptional group-law lanes (crafted scalar collisions):
                # the fast device adds deferred them; resolve exactly on
                # host (never hit by honest traffic —
                # tests/test_pallas_kernel.py crafts one).
                _HOST_FIXUPS.inc(int(needs_np.sum()))
                for i in np.nonzero(needs_np)[0]:
                    r = self._host_check(checks[start + int(i)])
                    out[start + int(i)] = r
                    if not r:
                        self._fixup_failed = True

    def _note_device_verdict(self, all_ok, ok, needs, count: int) -> None:
        """Settle-time hook: a device chunk passed every guard. The base
        verifier keeps no chunk-level verdict; the sharded subclass ANDs
        into its block verdict here — at settle, so retries and contained
        faults can never double- or mis-count."""

    def _note_host_lanes(self, results: np.ndarray) -> None:
        """Settle-time hook: a contained chunk resolved host-exact."""

    def pad(self, n: int) -> int:
        """Public pad-ladder size for `n` lanes (the index-mode batch
        driver packs lanes natively and needs the same padded shapes)."""
        return self._pad(n)

    @property
    def chunk(self) -> int:
        return self._chunk

    @property
    def lane_capacity(self) -> int:
        """Real lanes per chunk dispatch: one short of `chunk`, so the
        reserved known-answer lane never pushes a full chunk up a pad
        rung (8191 real lanes + 1 sentinel pad to 8192, not 16384)."""
        return self._chunk - 1

    def dispatch_lanes(self, args: Tuple, n: int):
        """Async-dispatch one lane batch (the prep_pack 7-tuple, already
        padded); returns an opaque pending handle for sync_lanes.
        The index-mode driver's seam: lanes are prepped in the native
        session (uniq_lanes) so no SigCheck objects exist on this side."""
        self._make_room()
        with self.phases("dispatch"):
            return self._dispatch_guarded(args, n)

    def sync_lanes(self, pending, n: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Materialize a dispatch_lanes result: (ok[:n], needs_host[:n] or
        None). Lanes flagged needs_host hit an exceptional group-law case
        (crafted scalar collisions) OR a contained device fault; the
        caller must resolve them exactly (nat_session_uniq_host_verify) —
        they report ok=False here. A chunk no device rung could answer for
        comes back with EVERY lane flagged needs_host (fail-closed: the
        caller's exact oracle decides, a fault never yields an ACCEPT)."""
        with self.phases("sync"):
            settled = self._settle_device(pending, n)
            if settled is None:
                return np.zeros(n, dtype=bool), np.ones(n, dtype=bool)
            ok, needs = settled
            return ok[:n], (None if needs is None else needs[:n])

    def _host_check(self, chk: SigCheck) -> bool:
        """Host-exact resolution of one check (native core when present,
        pure-Python oracle otherwise)."""
        if self._native is not None:
            ns = self._native.NativeSecp
            if chk.kind == "ecdsa":
                return ns.verify_ecdsa(*chk.data)
            if chk.kind == "schnorr":
                return ns.verify_schnorr(*chk.data)
            return ns.tweak_add_check(*chk.data)
        from . import secp_host

        if chk.kind == "ecdsa":
            return secp_host.verify_ecdsa(*chk.data)
        if chk.kind == "schnorr":
            return secp_host.verify_schnorr(*chk.data)
        return secp_host.xonly_tweak_add_check(*chk.data)

    def _pack_lanes(self, lanes: List["_Lane"]):
        n = len(lanes)
        size = self._pad(n)
        raw = bytearray(size * 4 * 32)
        pos = 0
        for lane in lanes:
            raw[pos : pos + 32] = lane.a.to_bytes(32, "little")
            raw[pos + 32 : pos + 48] = lane.b1.to_bytes(16, "little")
            raw[pos + 48 : pos + 64] = lane.b2.to_bytes(16, "little")
            raw[pos + 64 : pos + 96] = lane.px.to_bytes(32, "little")
            raw[pos + 96 : pos + 128] = lane.t1.to_bytes(32, "little")
            pos += 128
        # View over the bytearray, not a bytes copy: the fields array must
        # stay writable so install_sentinels can seed the pad region.
        fields = np.frombuffer(raw, dtype=np.uint8).reshape(size, 4, 32)

        def flag(get, pad_value):
            arr = np.fromiter((get(l) for l in lanes), dtype=np.int32, count=n)
            return np.concatenate([arr, np.full(size - n, pad_value, np.int32)])

        want_odd = flag(lambda l: l.want_odd, 0)
        parity = flag(lambda l: l.parity, -1)
        has_t2 = flag(lambda l: l.has_t2, 0)
        neg1 = flag(lambda l: l.neg1, 0)
        neg2 = flag(lambda l: l.neg2, 0)
        valid = np.zeros(size, dtype=bool)
        valid[:n] = [lane.valid for lane in lanes]
        return fields, want_odd, parity, has_t2, neg1, neg2, valid

    def _note_dispatch(self, padded: int, n: int, backend: str) -> bool:
        """Dispatch accounting — called around, never inside, the jit'd
        program, so kernel jaxprs are identical with telemetry on.
        Returns whether this is the padded shape's first dispatch."""
        _DISPATCH_TOTAL.inc(backend=backend)
        _DISPATCH_LANES.inc(n)
        _DISPATCH_PADDED.inc(padded)
        if padded:
            _DISPATCH_FILL.set(n / padded)
        first = padded not in self._seen_shapes
        if first:
            self._seen_shapes.add(padded)
            _NEW_SHAPES.inc()
        return first

    @staticmethod
    def _note_tiles(rows_a_program: int, programs: int = 1) -> None:
        """Count the grid steps `programs` Pallas programs of
        `rows_a_program` lanes each will run (a mesh dispatch: one program
        a shard), by the tile `verify_tiles` chooses for that size."""
        from ..ops.pallas_kernel import tile_grid

        sublanes, _, steps = tile_grid(rows_a_program)
        _DISPATCH_TILES.inc(steps * programs, rows=str(sublanes))

    def _launch_timed(self, packed: np.ndarray, n: int, backend: str):
        """Account for and launch one packed dispatch on `backend`, timing
        the launch itself (a shape's first launch traces and compiles
        before it enqueues): the program called on the host buffer, its
        one put made inside the call (0.29 ms on the chip's host against
        0.27 + 0.16 for a `device_put` and the call on its result: my chip
        run, PR 43), and the request for the result's copy to the host,
        behind the kernel, so that the settle finds it there."""
        padded = int(packed.shape[0])
        first = self._note_dispatch(padded, n, backend)
        t0 = _monotonic()
        result = _packed_program(backend)(packed)
        _TRANSFERS.inc(dir="in")
        result.copy_to_host_async()
        _TRANSFERS.inc(dir="out")
        _LAUNCH_SECONDS.set(
            _monotonic() - t0, backend=backend, padded=str(padded),
            which="first" if first else "warm",
        )
        return result

    def _run_packed(self, packed: np.ndarray, n: int):
        """Dispatch seam: start the device program of the current rung on
        one packed buffer (`lane_wire`). `n` is the count of real
        (unpadded) lanes. Returns the (async) device result,
        `int32[padded + 2]`: `ok + 2 * needs_host` a row (the pallas
        fast-add kernel defers lanes, resolved host-side; the XLA
        complete-add kernel none) and the checksum pair."""
        padded = int(packed.shape[0])
        _faults.maybe_raise("jax_backend.dispatch")
        if self._use_pallas and self._dispatch_level != "xla":
            # LANE_TILE is the kernel's own tile so the guard cannot drift
            # from its assert. A ladder-quarantined pallas rung skips
            # straight to XLA.
            from ..ops.pallas_kernel import LANE_TILE

            if padded % LANE_TILE == 0:
                self._note_tiles(padded)
                return self._launch_timed(packed, n, "pallas")
        return self._launch_timed(packed, n, "xla")

    # Convenience single-check wrappers (used by tests/differential fuzzing).
    def verify_ecdsa(self, pubkey: bytes, sig_der: bytes, msg32: bytes) -> bool:
        return bool(self.verify_checks([SigCheck("ecdsa", (pubkey, sig_der, msg32))])[0])

    def verify_schnorr(self, pubkey32: bytes, sig64: bytes, msg32: bytes) -> bool:
        return bool(
            self.verify_checks([SigCheck("schnorr", (pubkey32, sig64, msg32))])[0]
        )

    def tweak_add_check(
        self, tweaked32: bytes, parity: int, internal32: bytes, tweak32: bytes
    ) -> bool:
        return bool(
            self.verify_checks(
                [SigCheck("tweak", (tweaked32, parity, internal32, tweak32))]
            )[0]
        )


_default: Optional[TpuSecpVerifier] = None


def default_verifier() -> TpuSecpVerifier:
    """Process-wide verifier (compiled kernels are shared via jit cache)."""
    global _default
    if _default is None:
        _default = TpuSecpVerifier()
    return _default
