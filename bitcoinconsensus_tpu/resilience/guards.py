"""Verdict guards: validate every device return, sentinel known-answer lanes.

The device's answer to "is this spend valid" is a buffer that crossed a
runtime, a compiler, and a wire. Before the pipeline treats it as a
consensus verdict it must survive:

1. **Structural validation** (`validate_verdict`): the buffer has exactly
   the dispatched lane count, every element is finite, and every element
   is in the verdict domain {0, 1}. A truncated buffer, a NaN, or a 7
   raises ``VerdictAnomaly`` — the dispatching layer then contains the
   fault by re-verifying the affected lanes on the exact host oracle.
2. **Sentinel lanes** (`install_sentinels` / `SentinelSet.check`):
   known-answer EC checks written into the *pad region* of the packed
   batch — the lanes the pad ladder was going to waste anyway, so
   sentinels cost zero extra device work. Each sentinel is an
   R = (a+b)·G identity with a precomputed expected verdict (half expect
   True, half expect a deliberately-wrong target → False). A dispatch
   whose sentinel verdicts disagree with expectation proves the kernel,
   the runtime, or the readback corrupted the buffer *systematically*,
   and the whole chunk demotes to host.

Containment floor (closed as of the in-flight dispatch PR): sentinels
catch whole-buffer corruption classes (inversion, garbage, encoding
faults, dead kernels), structural validation catches anything
non-boolean, and the **verdict checksum** (`check_checksum`) closes the
remaining gap: a device-side (count, position-weighted) sum over the
verdict buffer, computed inside the dispatch's one program and compared at
settle against the same sums recomputed from the materialized buffer. Any single-lane
flip — sentinel region or real-lane region — changes the count by ±1
and mismatches; `flip` is a hard pass criterion in the chaos sweep.
Sentinel templates additionally *rotate* across dispatches
(`install_sentinels`), so a replayed/stuck verdict buffer that answers
the previous dispatch's pattern is caught; the dispatch layer pads every
shape with at least one spare lane (`TpuSecpVerifier._pad`) and packs the
(read-only) native buffers into one fresh buffer (`crypto/lane_wire.py`),
whose views take the sentinels, so no dispatch goes out sentinel-less.

Cache audit mode (`set_cache_audit`): when armed, the batch driver
re-verifies cache hits against the host oracle and evicts proven-wrong
entries — the containment story for poisoned cache entries, priced as an
opt-in because it re-pays the work the cache exists to skip.

Everything here is host-side numpy on materialized buffers — nothing is
traced, no kernel jaxpr changes.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..crypto import secp_host
from ..crypto.glv import split_lambda
from ..obs import counter as _obs_counter

__all__ = [
    "CHECKSUM_MOD",
    "SentinelSet",
    "VerdictAnomaly",
    "audit_cache_hits",
    "check_checksum",
    "check_sentinels",
    "install_sentinels",
    "install_sentinels_at",
    "set_cache_audit",
    "validate_verdict",
    "verdict_checksum_host",
]

GUARD_ANOMALIES = _obs_counter(
    "consensus_resilience_guard_anomalies_total",
    "device verdict buffers rejected by the guards, by site and reason",
    ("site", "reason"),
)
_SENTINEL_LANES = _obs_counter(
    "consensus_resilience_sentinel_lanes_total",
    "known-answer sentinel lanes mixed into device dispatches",
)
_SENTINEL_SKIPPED = _obs_counter(
    "consensus_resilience_sentinel_skipped_total",
    "dispatches that could not carry sentinels (no pad room or "
    "read-only packed buffers), by reason",
    ("reason",),
)
CONTAINED = _obs_counter(
    "consensus_resilience_contained_total",
    "faults contained by demoting work to the host-exact oracle, by site",
    ("site",),
)
HOST_EXACT_LANES = _obs_counter(
    "consensus_resilience_host_exact_lanes_total",
    "lanes re-verified on the host-exact oracle due to fault containment",
)
CACHE_POISON_CAUGHT = _obs_counter(
    "consensus_resilience_cache_poison_caught_total",
    "cache hits whose audit re-verification disagreed (entry evicted)",
    ("cache",),
)


class VerdictAnomaly(RuntimeError):
    """A device verdict buffer failed validation (reason in `.reason`)."""

    def __init__(self, site: str, reason: str, detail: str = ""):
        msg = f"verdict anomaly at {site}: {reason}"
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)
        self.site = site
        self.reason = reason
        # Single choke point for guard convictions: every anomaly lands
        # in the flight ring; a checksum mismatch (verdict corruption in
        # transit) is dump-worthy on its own, before the ladder reacts.
        from ..obs import flight as _flight

        _flight.record("guard.anomaly", site=site, reason=reason,
                       detail=detail)
        if reason == "checksum":
            _flight.trigger("checksum", site=site, detail=detail)


def validate_verdict(arr, n: int, site: str) -> np.ndarray:
    """Validate a materialized device verdict buffer; return it as bool.

    `n` is the exact lane count the buffer must have (padded size at the
    dispatch layer). Raises ``VerdictAnomaly`` — after counting it in
    ``consensus_resilience_guard_anomalies_total`` — on wrong shape,
    non-finite values, or values outside {0, 1}. Bool input is the
    trusted fast path: one asarray, no value scan.
    """
    a = np.asarray(arr)
    if a.ndim != 1 or a.shape[0] != n:
        GUARD_ANOMALIES.inc(site=site, reason="shape")
        raise VerdictAnomaly(site, "shape", f"got {a.shape}, want ({n},)")
    if a.dtype == np.bool_:
        return a
    if np.issubdtype(a.dtype, np.floating):
        if not np.isfinite(a).all():
            GUARD_ANOMALIES.inc(site=site, reason="nonfinite")
            raise VerdictAnomaly(site, "nonfinite")
    elif not np.issubdtype(a.dtype, np.integer):
        GUARD_ANOMALIES.inc(site=site, reason="dtype")
        raise VerdictAnomaly(site, "dtype", str(a.dtype))
    in_domain = (a == 0) | (a == 1)
    if not in_domain.all():
        GUARD_ANOMALIES.inc(site=site, reason="domain")
        raise VerdictAnomaly(
            site, "domain", f"{int((~in_domain).sum())} lanes outside {{0,1}}"
        )
    return a != 0


# --- sentinel lanes ---------------------------------------------------------
#
# Each template is a fully packed lane (the 128-byte field block + flags)
# plus its precomputed expected verdict. The check is R = a·G + b·G against
# target t1: for expect-True lanes t1 = ((a+b)·G).x; for expect-False lanes
# t1 = that x plus one (never a valid x for this R — the curve has no two
# points sharing R's orbit at x and x+1 for our fixed scalars, and equality
# is exact integer compare). b ships GLV-split exactly like a real lane, so
# sentinels exercise the same split/digit/ladder path real traffic does.

_SENTINEL_SCALARS = ((2, 3, True), (5, 7, False), (11, 13, True), (17, 19, False))
_templates: Optional[List[Tuple[bytes, int, int, int, int, int, bool]]] = None


def _sentinel_templates():
    """Lazily build packed sentinel rows (host EC math runs once/process)."""
    global _templates
    if _templates is not None:
        return _templates
    rows = []
    for a, b, expect in _SENTINEL_SCALARS:
        aff = secp_host.G.mul((a + b) % secp_host.N).to_affine()
        rx = aff[0]
        t1 = rx if expect else (rx + 1) % secp_host.P
        b1, neg1, b2, neg2 = split_lambda(b)
        raw = (
            a.to_bytes(32, "little")
            + b1.to_bytes(16, "little")
            + b2.to_bytes(16, "little")
            + secp_host.G_X.to_bytes(32, "little")
            + t1.to_bytes(32, "little")
        )
        want_odd = secp_host.G_Y & 1
        rows.append((raw, want_odd, -1, 0, neg1, neg2, expect))
    _templates = rows
    return rows


class SentinelSet:
    """Positions + expected verdicts of the sentinels in one dispatch."""

    __slots__ = ("positions", "expected")

    def __init__(self, positions: List[int], expected: List[bool]):
        self.positions = np.asarray(positions, dtype=np.int64)
        self.expected = np.asarray(expected, dtype=bool)

    def check(self, ok: np.ndarray, needs: Optional[np.ndarray], site: str) -> None:
        """Compare sentinel verdicts against expectation; raise on mismatch.

        Lanes the fast-add kernel flagged `needs_host` report ok=False by
        design regardless of the true answer, so flagged sentinels are
        excluded rather than miscounted as corruption.
        """
        got = np.asarray(ok, dtype=bool)[self.positions]
        exp = self.expected
        if needs is not None:
            usable = ~np.asarray(needs, dtype=bool)[self.positions]
            got, exp = got[usable], exp[usable]
        if not np.array_equal(got, exp):
            GUARD_ANOMALIES.inc(site=site, reason="sentinel")
            raise VerdictAnomaly(
                site,
                "sentinel",
                f"expected {exp.tolist()}, got {got.tolist()}",
            )


_rotation = 0


def install_sentinels(
    args: Tuple, n: int, rotation: Optional[int] = None
) -> Optional[SentinelSet]:
    """Write sentinel lanes into the pad region of a packed batch, in place.

    `args` is the kernel's 7-tuple (fields, want_odd, parity, has_t2,
    neg1, neg2, valid), as separate arrays or as the views over one packed
    buffer (`lane_wire._lane_views`); `n` is the real lane count, so rows
    [n, size) are pad. Templates rotate across dispatches (a process-wide
    counter advances the starting template each call) so consecutive
    batches of the same shape carry *different* expected patterns — a
    stuck or replayed verdict buffer that answers the previous dispatch's
    pattern mismatches. Pass `rotation` to pin the phase (tests).

    Returns the SentinelSet to check at settle, or None (counted) when
    the batch has no pad room or the buffers are not writable (the
    dispatch layer writes through the views of its own fresh buffer).
    """
    fields = args[0]
    size = int(fields.shape[0])
    room = size - n
    if room <= 0:
        _SENTINEL_SKIPPED.inc(reason="no_pad_room")
        return None
    k = min(room, len(_sentinel_templates()))
    return install_sentinels_at(args, list(range(n, n + k)), rotation)


def install_sentinels_at(
    args: Tuple, positions: Sequence[int], rotation: Optional[int] = None
) -> Optional[SentinelSet]:
    """Write sentinel lanes at explicit row positions, in place.

    The scatter-layout variant of ``install_sentinels``: the sharded
    verifier reserves the *last* lane of every device shard rather than
    a contiguous tail region, so each shard carries its own known-answer
    lane and a per-shard flip is localized to that shard. Template
    selection still rotates (one process-wide counter advance per call,
    templates cycle across `positions`), so consecutive dispatches carry
    different expected patterns per shard.

    Returns None (counted) when the buffers are not writable.
    """
    global _rotation
    fields, want_odd, parity, has_t2, neg1, neg2, valid = args
    arrs = (fields, want_odd, parity, has_t2, neg1, neg2, valid)
    if not all(getattr(a, "flags", None) is not None and a.flags.writeable
               for a in arrs):
        _SENTINEL_SKIPPED.inc(reason="readonly")
        return None
    templates = _sentinel_templates()
    if rotation is None:
        rotation = _rotation
        _rotation = (_rotation + 1) % len(templates)
    out_pos, expected = [], []
    for i, pos in enumerate(positions):
        raw, w, par, h2, n1, n2, exp = templates[(rotation + i) % len(templates)]
        fields[pos] = np.frombuffer(raw, dtype=np.uint8).reshape(4, 32)
        want_odd[pos] = w
        parity[pos] = par
        has_t2[pos] = h2
        neg1[pos] = n1
        neg2[pos] = n2
        valid[pos] = True
        out_pos.append(int(pos))
        expected.append(exp)
    _SENTINEL_LANES.inc(len(out_pos))
    return SentinelSet(out_pos, expected)


def check_sentinels(
    sset: Optional[SentinelSet],
    ok: np.ndarray,
    needs: Optional[np.ndarray],
    site: str,
) -> None:
    """Module-level convenience: no-op for sentinel-less dispatches."""
    if sset is not None:
        sset.check(ok, needs, site)


# --- verdict checksum -------------------------------------------------------
#
# The single-flip detector. The dispatch's program ends in a tiny
# reduction over its pristine verdict buffer: (sum of lanes, sum of
# lane·weight) with weight[i] = i % CHECKSUM_MOD + 1, the tail of its result. At settle the same
# two sums are recomputed host-side from the materialized buffer and must
# match exactly. Any single-lane flip changes the count sum by ±1; the
# weighted sum localizes most multi-lane corruptions the count parity
# would miss. int32-safe on device: 252 · B < 2^31 for B up to ~8.5M
# lanes (the interval prover certifies the registered kernel).

CHECKSUM_MOD = 251


def verdict_checksum_host(ok: np.ndarray) -> Tuple[int, int]:
    """Host recomputation of the device verdict checksum (int64 math)."""
    v = np.asarray(ok).astype(np.int64)
    w = np.arange(v.shape[0], dtype=np.int64) % CHECKSUM_MOD + 1
    return int(v.sum()), int((v * w).sum())


def check_checksum(
    device_sums: Optional[Tuple[int, int]], ok: np.ndarray, site: str
) -> None:
    """Compare device-side verdict sums against the materialized buffer.

    `device_sums` is the materialized (count, weighted) pair the dispatch
    layer computed on-device over the same buffer; None means the
    dispatch carried no checksum (counted as a guard skip is not needed —
    the caller decides whether checksum-less dispatch is allowed). Raises
    ``VerdictAnomaly(reason="checksum")`` on mismatch.
    """
    if device_sums is None:
        return
    count, wsum = verdict_checksum_host(ok)
    dev = (int(device_sums[0]), int(device_sums[1]))
    if dev != (count, wsum):
        GUARD_ANOMALIES.inc(site=site, reason="checksum")
        raise VerdictAnomaly(
            site, "checksum", f"device {dev} vs host {(count, wsum)}"
        )


# --- cache audit mode -------------------------------------------------------

_audit_cache = False


def set_cache_audit(on: bool) -> None:
    """Arm/disarm cache-hit auditing (poisoned-entry containment).

    When armed, the batch driver re-verifies every signature-cache hit
    against the host-exact oracle and evicts entries that disagree
    (counted in ``consensus_resilience_cache_poison_caught_total``).
    Off by default: auditing re-pays exactly the work the cache skips.
    """
    global _audit_cache
    _audit_cache = bool(on)


def audit_cache_hits() -> bool:
    return _audit_cache
