"""Multi-chip scaling: batch sharding over a device mesh + XLA collectives.

The reference has no distributed backend at all — its parallel axis is a
thread pool draining per-input checks (`checkqueue.h:29-163`). The TPU-native
equivalent (SURVEY §2.2) shards the *signature-check batch* across chips:

- a 1-D ``Mesh`` over a ``batch`` axis (data parallelism is the only axis
  with meaning here: lanes are independent; there is no gradient/activation
  traffic analogue),
- ``jax.jit`` with ``NamedSharding`` in/out specs so XLA partitions the
  verify kernel SPMD across the mesh (collective-free: embarrassingly
  parallel compute),
- a ``shard_map`` reduction step that AND-reduces per-lane verdicts into a
  block-level verdict with ``psum`` over ICI — the analogue of
  `CCheckQueueControl::Wait()`'s all-inputs-valid barrier
  (`checkqueue.h:139-142,188-195`).

Where `CCheckQueueControl::Wait()` assumes every worker answers, a mesh
must not: this module gives every device shard its own **fault domain**.
Each shard reserves the *last* lane of its slice for a rotating
known-answer sentinel, the sharded step returns a per-shard verdict
checksum pair (lane count + mod-251 position-weighted sum, computed
inside `shard_map` and recomputed host-side at settle), and the settle
seam validates shards *independently*: a flip on chip 3 is localized to
chip 3, whose lanes alone re-dispatch (surviving mesh → single-device
XLA → host-exact) while the other seven shards' verdicts stand. A
persistently sick device is *evicted* — the mesh is rebuilt and the
sharded step re-jitted over the survivors (`ShardLadder` in
`resilience/degrade.py`) — and later re-probed with a known-answer batch
for re-promotion. Per-shard stragglers have their own deadline
(`BITCOINCONSENSUS_TPU_SHARD_DEADLINE_S`), distinct from the whole-ticket
deadline of the in-flight queue.

A dispatch crosses the host-device seam in as few pieces as the mesh has
shards, each way: its lanes travel as ONE packed byte buffer (`ROW_BYTES` a
lane: the field bytes, the five flags, `valid`, `live`; `pack_lanes`,
`unpack_lanes`: `crypto/lane_wire.py`, the one-chip verifier's format
too), unpacked by the first ops of the sharded program, and its
verdicts, deferral mask, checksum pairs and psum verdict come back as ONE
int32 array (`unpack_result`). A launch costs by the piece, not by the byte
(PERF.md section 6, PRs 33 and 35).

Telemetry of its own: phases `shard_layout` (the layout and sentinel install
of `_prepare_ticket`), `shard_put` (the packed buffer put to the shards) and
`shard_exec` (the program started, its result's host copy asked for), all
inside `dispatch`, and `shard_check` (what a settle does once the result is
on the host, inside `sync` or `backpressure`) on `verifier.phases`; spans
`mesh.dispatch` and `mesh.settle` (shards, lanes, epoch);
`consensus_mesh_dispatch_total` by the `kernel` every shard ran
(`shard_kernel`); `consensus_mesh_transfers_total` by direction, a count a
piece; and the jitted step's stable program name, `jit_mesh_verify_tiles`
on a TPU mesh.

Multi-host: the same mesh spec over `jax.devices()` spanning hosts rides
ICI/DCN transparently through pjit — no NCCL/MPI translation layer exists or
is needed.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..crypto.jax_backend import (
    SigCheck,
    TpuSecpVerifier,
    _packed_program,
    _verdict_checksum,
    _verify_kernel,
)
from ..crypto.lane_wire import (  # re-exported: the format's names live on here
    CHECKSUM_TAIL,
    ROW_BYTES,
    _FIELD_BYTES,
    _PAD_FLAGS,
    _PAD_VALUES,
    _lane_views,
    _unpack_lanes_traced,
    pack_lanes,
    pack_result_traced,
    pad_rows,
    split_result,
    unpack_lanes,
)
from ..obs import counter as _obs_counter
from ..obs import gauge as _obs_gauge
from ..obs import histogram as _obs_histogram
from ..obs import monotonic as _monotonic
from ..obs import span as _obs_span
from ..resilience import degrade as _degrade
from ..resilience import faults as _faults
from ..resilience import guards as _guards
from ..ops.regions import region_scope
from ..resilience.inflight import settle_array

__all__ = [
    "make_mesh", "ShardedSecpVerifier", "make_sharded_step", "shard_kernel",
    "ROW_BYTES", "pack_lanes", "unpack_lanes", "unpack_result",
]

# Mesh telemetry — host-side driver accounting only; `local_step` below is
# traced and must stay instrumentation-free.
_MESH_DEVICES = _obs_gauge(
    "consensus_mesh_devices", "devices in the sharded verifier's mesh"
)
_MESH_DISPATCH = _obs_counter(
    "consensus_mesh_dispatch_total",
    "sharded (multi-chip) dispatches, by the kernel every shard ran "
    "(`shard_kernel`: pallas, or xla where the tile does not divide the shard)",
    ("kernel",),
)
_MESH_TRANSFERS = _obs_counter(
    "consensus_mesh_transfers_total",
    "host-device transfers of sharded dispatches, a count a piece (one array "
    "on one shard), by direction: in = arguments put, out = results' host "
    "copies asked for",
    ("dir",),
)
_MESH_SHARD_LANES = _obs_histogram(
    "consensus_mesh_shard_lanes",
    "live (real, non-sentinel/pad) lanes per device shard per dispatch",
    buckets=(8, 64, 512, 4096, 32768),
)
_MESH_SHARD_FAILURES = _obs_counter(
    "consensus_mesh_shard_failures_total",
    "per-shard settle failures (guard anomaly, checksum mismatch, "
    "straggler deadline, device loss), by device and reason",
    ("device", "reason"),
)
_MESH_EVICTIONS = _obs_counter(
    "consensus_mesh_evictions_total",
    "devices evicted from the mesh after repeated shard failures",
    ("device",),
)
_MESH_REPROMOTIONS = _obs_counter(
    "consensus_mesh_repromotions_total",
    "evicted devices re-promoted into the mesh after a clean probe",
    ("device",),
)
_MESH_VERDICT_MISMATCH = _obs_counter(
    "consensus_mesh_verdict_mismatch_total",
    "cleanly settled mesh dispatches whose replicated psum verdict differed "
    "from the AND of their settled real lanes",
)
_MESH_REDISPATCH_LANES = _obs_counter(
    "consensus_mesh_redispatch_lanes_total",
    "lanes re-dispatched after their shard failed settle, by the level "
    "that answered (mesh = surviving shards, xla = single device, "
    "host = exact oracle)",
    ("level",),
)


def make_mesh(
    n_devices: Optional[int] = None,
    axis: str = "batch",
    devices: Optional[Sequence] = None,
) -> Mesh:
    """1-D device mesh over the batch axis.

    Pass `devices` to build over an explicit device list (the elastic
    verifier rebuilds over eviction survivors this way). Asking for more
    devices than the platform has is an error, not a silent truncation —
    a deployment that believes it runs 8-wide must not quietly run 1-wide.
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if n_devices > len(devices):
                raise ValueError(
                    f"make_mesh: requested {n_devices} devices but only "
                    f"{len(devices)} are available "
                    f"(platform {devices[0].platform})"
                )
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def shard_kernel(use_pallas: bool, shard_rows: int) -> str:
    """The kernel a shard of `shard_rows` lanes runs, "pallas" or "xla":
    the SAME backend selection as TpuSecpVerifier._run_packed, applied to
    the shard-local batch (so a multi-chip deployment dispatches the Pallas
    production kernel on each chip; CPU meshes and tile-indivisible shards
    fall back to XLA). The traced step and the dispatch counter's `kernel`
    label both ask here, so the label cannot drift from what ran."""
    if use_pallas:
        from ..ops.pallas_kernel import LANE_TILE

        if shard_rows % LANE_TILE == 0:
            return "pallas"
    return "xla"


def _pick_backend(use_pallas: bool):
    """Per-shard kernel by `shard_kernel`."""

    def local_kernel(fields, want_odd, parity_req, has_t2, neg1, neg2, valid):
        # Shard-local shapes are static at trace time inside shard_map.
        if shard_kernel(use_pallas, fields.shape[0]) == "pallas":
            from ..ops.pallas_kernel import verify_tiles

            return verify_tiles(
                fields, want_odd, parity_req, has_t2, neg1, neg2, valid
            )
        ok = _verify_kernel(
            fields, want_odd, parity_req, has_t2, neg1, neg2, valid
        )
        return ok, jnp.zeros_like(ok)  # complete-add kernel: no deferrals

    return local_kernel


# --- the packed wire format ---------------------------------------------
#
# `crypto/lane_wire.py` holds the format both verifiers send: `ROW_BYTES` a
# lane in, `ok + 2 * needs_host` a row and a tail out. What the mesh adds:
# the rows lie shard-major (`_build_layout`), a row's `live` byte says
# whether the psum verdict counts it, and a dispatch's result is one int32
# array, shard after shard, each shard's tail its checksum pair (count,
# weighted sum) and the psum verdict every shard holds a copy of.

_RESULT_TAIL = CHECKSUM_TAIL + 1  # count, weighted sum, psum verdict


def unpack_result(raw: np.ndarray, n_shards: int):
    """A settled packed result, on the host (`settle_array`), as `(ok,
    needs_host, all_ok, counts, wsums)`: the padded verdict buffer (bool),
    the deferral mask (int32, so that a row outside {0..3} fails the
    shard's domain guard and is not masked away), the psum verdict (False
    unless every shard's copy says so) and each shard's checksum pair.
    Raises ValueError on a buffer that does not split `n_shards` ways."""
    ok, needs, tail = split_result(raw, n_shards, _RESULT_TAIL)
    return ok, needs, bool((tail[:, 2] == 1).all()), tail[:, 0], tail[:, 1]


def make_sharded_step(mesh: Mesh, use_pallas: Optional[bool] = None):
    """The full multichip verify step, jitted over `mesh`.

    Returns ``step(packed) -> result``: one argument in, one array out, so
    that a launch makes one transfer a shard each way. `packed` is the
    batch as `pack_lanes` lays it out (uint8, `ROW_BYTES` a lane),
    batch-sharded; the first ops on every shard slice and widen it to the
    kernel's seven arguments and the `live` mask (`unpack_lanes` is their
    host twin). `result` is one int32 array, batch-sharded, `S + 3`
    entries a shard of `S` rows (`unpack_result`): `ok + 2 * needs_host`
    a row; the shard's verdict checksum pair, computed on-device over the
    shard-local verdict slice (`jax_backend._verdict_checksum`, so the
    interval prover's coverage rides along); and `all_ok`, produced by a
    psum AND-reduction inside shard_map (the cross-chip collective — the
    `CCheckQueueControl::Wait` analogue, checkqueue.h:139-142), of which
    every shard carries a copy. The settle seam recomputes both sums
    host-side per shard; a mismatch convicts exactly that shard. `live`
    marks real lanes: padding added to reach the batch shape is not
    counted as a failure, while structurally-invalid real lanes are.
    `needs_host` lanes (exceptional group-law deferrals of the pallas fast
    adds) are excluded from the device verdict — the host resolves them
    exactly and adjusts. Each shard runs the production backend selection
    (Pallas on TPU when the local tile divides; XLA otherwise).
    """
    axis = mesh.axis_names[0]
    if use_pallas is None:
        use_pallas = all(d.platform == "tpu" for d in mesh.devices.flat)
    local_kernel = _pick_backend(use_pallas)

    def local_step(packed):
        # region scope only — metadata: the op's name in a profiler
        # trace; the traced program is unchanged.
        with region_scope("shard_step"):
            *lanes, live = _unpack_lanes_traced(packed)
            per_lane, needs = local_kernel(*lanes)
            # all-valid <=> no live lane DEFINITELY failed, on any shard
            # (deferred lanes stay out; the host fixup ANDs their
            # verdicts in).
            failures = jnp.sum(jnp.where(live & ~per_lane & ~needs, 1, 0))
            all_ok = jax.lax.psum(failures, axis) == 0
            # the checksum pair over the pristine verdict slice
            cnt, wsum = _verdict_checksum(per_lane)
            return pack_result_traced(
                per_lane, needs, [cnt, wsum, all_ok.astype(jnp.int32)]
            )

    # Varying-axes checking is off: the verify kernel's scan carries start
    # as mesh-wide constants (infinity masks, G-table selects) and become
    # shard-varying inside the loop — correct SPMD, but the strict
    # varying-axes tracker rejects the carry-type mismatch.
    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis),
        check_vma=False,
    )

    def step(packed):
        return sharded(packed)

    # The program's name in a profiler trace (`XLA Modules`: `jit_<name>`),
    # after the kernel the shards run where the tile divides them.
    step.__name__ = step.__qualname__ = (
        "mesh_verify_tiles" if use_pallas else "mesh__verify_kernel"
    )
    return jax.jit(
        step,
        in_shardings=NamedSharding(mesh, P(axis, None)),
        out_shardings=NamedSharding(mesh, P(axis)),
    )


@functools.lru_cache(maxsize=16)
def _shard_positions(n: int, shard_size: int) -> np.ndarray:
    """Global row index of real lane `i` under the scatter layout.

    Each shard of `shard_size` rows holds `shard_size - 1` real lanes
    followed by its reserved sentinel row, so lane i lands at
    ``(i // (S-1)) * S + (i % (S-1))``. A round's chunks are of one or
    two sizes, so the array is kept (read-only: every layout of a size
    shares it).
    """
    cap = shard_size - 1
    idx = np.arange(n, dtype=np.int64)
    positions = (idx // cap) * shard_size + (idx % cap)
    positions.flags.writeable = False
    return positions


def _shard_fill(n: int, shard_size: int, n_shards: int) -> list:
    """Real lanes each shard holds under the scatter layout: full shards
    of `shard_size - 1` first, then the remainder, then empty ones."""
    cap = shard_size - 1
    return [min(max(n - s * cap, 0), cap) for s in range(n_shards)]


class _ShardLayout:
    """Settle context of one scattered mesh dispatch (rides ticket.sset).

    `positions` maps real-lane order to global rows (the rows whose `live`
    byte is set: the psum verdict counts no other); `ssets` holds one
    single-lane SentinelSet per shard (local position S-1) for per-shard
    checking, and `flat_sset` the same sentinels as one global set for
    the quarantined single-device fallback path. `epoch` pins the mesh
    generation the layout was built for: after an eviction rebuilds the
    mesh, stale layouts are no longer shard-aligned and relaunch on the
    single-device rung instead. `deadline_armed` is False for
    first-compile shapes, so the per-shard straggler deadline never fires
    on XLA compilation time.
    """

    __slots__ = (
        "n", "padded", "n_shards", "shard_size", "positions",
        "ssets", "flat_sset", "epoch", "deadline_armed",
    )

    def __init__(self, n, padded, n_shards, shard_size, positions,
                 ssets, flat_sset, epoch, deadline_armed):
        self.n = n
        self.padded = padded
        self.n_shards = n_shards
        self.shard_size = shard_size
        self.positions = positions
        self.ssets = ssets
        self.flat_sset = flat_sset
        self.epoch = epoch
        self.deadline_armed = deadline_armed


class ShardedSecpVerifier(TpuSecpVerifier):
    """Drop-in TpuSecpVerifier that spreads each dispatch over a mesh,
    with per-device fault domains: per-shard sentinels + checksums at
    settle, shard-granular re-dispatch, and elastic device eviction."""

    def __init__(self, mesh: Optional[Mesh] = None, min_batch: int = 8,
                 chunk: int = 1 << 13, evict_after: Optional[int] = None):
        super().__init__(min_batch=min_batch, chunk=chunk)
        mesh = mesh if mesh is not None else make_mesh()
        self._axis = mesh.axis_names[0]
        self._all_devices = list(mesh.devices.flat)
        self._base_min_batch = min_batch
        self._mesh_epoch = 0
        self._shard_ladder = _degrade.ShardLadder(
            [str(d.id) for d in self._all_devices], evict_after=evict_after
        )
        self._shard_deadline_s = float(os.environ.get(
            "BITCOINCONSENSUS_TPU_SHARD_DEADLINE_S", "4.0"
        ))
        self._verdict_acc = True
        self._dispatched = 0
        self._install_mesh(mesh)

    _SITE = "mesh"

    def _install_mesh(self, mesh: Mesh) -> None:
        """(Re)build the sharded step over `mesh`; logs the effective
        mesh size via obs (gauge + a traced `mesh.build` span) — also the
        eviction/re-promotion rebuild path, where re-jitting over the
        survivors is the dominant cost and worth a span of its own."""
        n = int(mesh.devices.size)
        self.mesh = mesh
        self._shard_device_ids = [str(d.id) for d in mesh.devices.flat]
        # Batch sizes must divide evenly across the mesh: round min_batch
        # up to a multiple of n (doubling in _pad preserves divisibility).
        self._min_batch = -(-self._base_min_batch // n) * n
        tpu_mesh = all(d.platform == "tpu" for d in mesh.devices.flat)
        self._mesh_pallas = self._use_pallas and tpu_mesh
        self._packed_sharding = NamedSharding(mesh, P(self._axis, None))
        with _obs_span("mesh.build", devices=n, epoch=self._mesh_epoch):
            self._step = make_sharded_step(mesh, use_pallas=self._mesh_pallas)
        _MESH_DEVICES.set(n)

    def _ladder_levels(self):
        # Quarantined mesh dispatch falls back to the single-device base
        # kernel before host: a sick collective/device drop does not force
        # host EC math while one chip still answers correctly.
        return ("mesh", "xla", _degrade.HOST_LEVEL)

    # --- layout ---------------------------------------------------------

    def _pad(self, n: int) -> int:
        # Reserve one sentinel lane PER SHARD (not one per dispatch): the
        # padded size must fit n real lanes plus n_devices sentinels, and
        # every shard must be >= 2 rows so its sentinel never crowds out
        # real work. min_batch is a multiple of n_devices, so doubling
        # preserves divisibility.
        d = int(self.mesh.devices.size)
        size = self._min_batch
        while size < n + d or size // max(d, 1) < 2:
            size *= 2
        return size

    @property
    def lane_capacity(self) -> int:
        """Real lanes per chunk dispatch: one short PER SHARD of `chunk`,
        so the per-shard sentinel rows never push a full chunk up a pad
        rung."""
        return self._chunk - int(self.mesh.devices.size)

    def _build_layout(self, args, n: int, padded: Optional[int] = None):
        """Lay `args`' first `n` lanes out shard-major in ONE fresh packed
        buffer of `padded` rows (default: as many as `args` has) and
        install the per-shard sentinels: `((packed,), layout)`, or None
        when the size cannot carry the layout (the caller falls back to
        the contiguous single-sentinel prep). Shard s holds lanes
        [s*cap, (s+1)*cap) at the head of its slice, so the scatter is two
        block copies a shard (its field bytes, its flag bytes), out of
        buffers that may be read-only (the native lane prep's arena): no
        copy is made before this one. The flags narrow to a byte each on
        the way (`pack_lanes`' format)."""
        d = int(self.mesh.devices.size)
        if padded is None:
            padded = int(args[0].shape[0])
        if d < 2 or padded % d or padded < n + d:
            return None
        shard = padded // d
        cap = shard - 1
        if shard < 2 or n > d * cap:
            return None
        packed = np.empty((padded, ROW_BYTES), dtype=np.uint8)
        fields = packed[:, :_FIELD_BYTES]
        flags = packed.view(np.int8)[:, _FIELD_BYTES:]
        src_fields = args[0].reshape(-1, _FIELD_BYTES)
        # A row's seven flag bytes go in as one block: a column at a time
        # would walk the whole buffer seven times.
        block = np.empty((cap, ROW_BYTES - _FIELD_BYTES), dtype=np.int8)
        block[:, -1] = 1  # live
        for s, k in enumerate(_shard_fill(n, shard, d)):
            row, lane = s * shard, s * cap
            fields[row : row + k] = src_fields[lane : lane + k]
            fields[row + k : row + shard] = _PAD_VALUES[0]
            for j, a in enumerate(args[1:]):
                block[:k, j] = a[lane : lane + k]
            flags[row : row + k] = block[:k]
            flags[row + k : row + shard] = _PAD_FLAGS
        sent_rows = [s * shard + cap for s in range(d)]
        flat = _guards.install_sentinels_at(
            _lane_views(packed)[:-1], sent_rows
        )
        if flat is None:
            return None
        ssets = [
            _guards.SentinelSet([cap], [bool(flat.expected[s])])
            for s in range(d)
        ]
        return (packed,), _ShardLayout(
            n, padded, d, shard, _shard_positions(n, shard), ssets,
            flat, self._mesh_epoch, padded in self._seen_shapes,
        )

    def _prepare_ticket(self, args, n: int):
        """Dispatch-time prep (inflight queue callback): lay the batch out
        shard-major with one rotating known-answer sentinel per device
        shard, timed as the `shard_layout` phase (it lies inside
        `dispatch`). Falls back to the base contiguous sentinel prep when
        the batch cannot shard."""
        with self.phases("shard_layout"):
            laid = self._build_layout(args, n)
        if laid is None:
            return TpuSecpVerifier._prepare_ticket(self, args, n)
        return laid

    # --- launch ---------------------------------------------------------

    def _launch_ticket(self, args, n: int, level: str, sset=None):
        """Launch one chunk (inflight queue callback). Mesh-level launches
        need a current-epoch shard layout; anything else (quarantined
        rung, stale layout after an eviction rebuild, unshardable batch)
        runs the single-device base dispatch on the same packed buffer
        (the base program does not read `live`), whose settle is guarded
        by the flat sentinel set + global checksum."""
        layout = sset if isinstance(sset, _ShardLayout) else None
        if (
            level != "mesh"
            or layout is None
            or layout.epoch != self._mesh_epoch
        ):
            if level == "mesh":
                level = "xla"
            return TpuSecpVerifier._launch_ticket(self, args, n, level, sset)
        _faults.maybe_raise("mesh.dispatch")
        with _obs_span("mesh.dispatch", shards=layout.n_shards, lanes=n,
                       epoch=layout.epoch):
            self._note_mesh_dispatch(layout)
            # Sentinel/pad lanes stay out of the psum (their `live` byte);
            # the per-shard checksums ride inside the one result: no aux.
            # The 1-tuple is what tells a mesh result from the base rungs'.
            return (self._run_step(args[0]),), None

    def _run_step(self, packed: np.ndarray):
        """Start the sharded program on one packed buffer: a piece a shard
        to the devices (`shard_put`), then the execute call and the
        request for the result's copy to the host, a piece a shard, behind
        the kernel, so that the settle finds it there (`shard_exec`)."""
        with self.phases("shard_put"):
            on_mesh = jax.device_put(packed, self._packed_sharding)
            _MESH_TRANSFERS.inc(on_mesh.sharding.num_devices, dir="in")
        with self.phases("shard_exec"):
            result = self._step(on_mesh)
            start_copy = getattr(result, "copy_to_host_async", None)
            if start_copy is not None:
                start_copy()
                _MESH_TRANSFERS.inc(result.sharding.num_devices, dir="out")
        return result

    def _note_mesh_dispatch(self, layout: _ShardLayout) -> None:
        """Dispatch accounting of one sharded launch of `layout`."""
        self._note_dispatch(layout.padded, layout.n, "mesh")
        kernel = shard_kernel(self._mesh_pallas, layout.shard_size)
        _MESH_DISPATCH.inc(kernel=kernel)
        if kernel == "pallas":
            self._note_tiles(layout.shard_size, layout.n_shards)
        for k in _shard_fill(layout.n, layout.shard_size, layout.n_shards):
            _MESH_SHARD_LANES.observe(k)

    # --- settle ---------------------------------------------------------

    def _materialize_guarded(self, ticket):
        result = ticket.result
        layout = ticket.sset if isinstance(ticket.sset, _ShardLayout) else None
        if layout is None:
            # Contiguous prep (unshardable batch): base settle seam.
            return TpuSecpVerifier._materialize_guarded(self, ticket)
        if not (isinstance(result, tuple) and len(result) == 1):
            return self._materialize_flat(ticket, layout)
        return self._materialize_sharded(ticket, layout)

    def _materialize_flat(self, ticket, layout: _ShardLayout):
        """Settle a scattered buffer answered by the single-device rung:
        whole-buffer guards (flat sentinels + global checksum), then
        gather real lanes back to caller order."""
        ok, needs = self._settle_packed(
            ticket.result, layout.padded, layout.flat_sset, seam=True
        )
        return ok[layout.positions], needs[layout.positions], None

    def _materialize_sharded(self, ticket, layout: _ShardLayout):
        """The per-shard settle seam (span `mesh.settle`): validate every
        device shard independently (structural guards, per-shard checksum
        FIRST — the single-flip detector — then the shard's sentinel),
        feed per-device health, and re-dispatch only the failed shards'
        lanes."""
        with _obs_span("mesh.settle", shards=layout.n_shards,
                       lanes=layout.n, epoch=layout.epoch):
            # The wait for the kernel is the caller's (`sync`,
            # `backpressure`); what the mesh adds to a settle on the host
            # is `shard_check`: the result unpacked, every shard checked,
            # the lanes gathered back to caller order.
            raw = settle_array(ticket.result[0])
            with self.phases("shard_check"):
                ok_np, needs_np, all_ok, cnts, wsums = self._unpack_settled(
                    raw, layout
                )
                ok_v, needs_v, bad = self._check_settled(
                    _faults.corrupt_verdict("jax_backend.verdict", ok_np),
                    needs_np, cnts, wsums, layout,
                    _monotonic() - ticket.born,
                )
                if not bad:
                    return self._settle_clean(layout, all_ok, ok_v, needs_v)
            return self._settle_partial(ticket, layout, ok_v, needs_v, bad)

    def _unpack_settled(self, raw: np.ndarray, layout: _ShardLayout):
        """`unpack_result` behind the whole-buffer shape guard."""
        if raw.shape != (layout.padded + _RESULT_TAIL * layout.n_shards,):
            _guards.GUARD_ANOMALIES.inc(site=self._SITE, reason="shape")
            raise _guards.VerdictAnomaly(
                self._SITE, "shape",
                f"got {raw.shape}, want {layout.n_shards} shards of "
                f"({layout.shard_size + _RESULT_TAIL},)",
            )
        return unpack_result(raw, layout.n_shards)

    def _check_settled(self, ok_np, needs_np, cnts_np, wsums_np,
                       layout: _ShardLayout, elapsed: float):
        """`_check_shards` behind the whole-buffer shape guard, with the
        per-device health report: `(ok, needs, bad)`."""
        if (
            ok_np.ndim != 1
            or ok_np.shape[0] != layout.padded
            or needs_np.shape != ok_np.shape
            or cnts_np.shape[0] != layout.n_shards
            or wsums_np.shape[0] != layout.n_shards
        ):
            _guards.GUARD_ANOMALIES.inc(site=self._SITE, reason="shape")
            raise _guards.VerdictAnomaly(
                self._SITE, "shape",
                f"got {ok_np.shape}/{cnts_np.shape}, "
                f"want ({layout.padded},)/({layout.n_shards},)",
            )
        ok_v, needs_v, bad = self._check_shards(
            ok_np, needs_np, cnts_np, wsums_np, layout, elapsed
        )
        # Per-device health feeds the eviction ladder at the PRIMARY
        # settle only (re-dispatch retries must not double-convict).
        # Evictions apply after the loop: each one rebuilds the mesh and
        # shrinks _shard_device_ids, which this loop still indexes by the
        # layout's (pre-eviction) shard count.
        devs = list(self._shard_device_ids)
        to_evict = []
        for s in range(layout.n_shards):
            dev = devs[s]
            if s in bad:
                _MESH_SHARD_FAILURES.inc(device=dev, reason=bad[s])
            if self._shard_ladder.report_shard(dev, s not in bad):
                to_evict.append(dev)
        for dev in to_evict:
            self._evict_device(dev)
        if len(bad) == layout.n_shards:
            # Nothing survived: whole-mesh fault — let the ticket's
            # retry/ladder policy decide (same as the pre-shard-domain
            # behavior).
            raise _guards.VerdictAnomaly(
                self._SITE, "all-shards", ",".join(sorted(set(bad.values())))
            )
        return ok_v, needs_v, bad

    def _settle_clean(self, layout: _ShardLayout, all_ok, ok_v, needs_v):
        """Every shard passed: the lanes in caller order and the psum
        collective's replicated verdict (counted where it is not the AND
        of the lanes it was reduced from)."""
        probe_dev = self._shard_ladder.note_clean_dispatch()
        if probe_dev is not None:
            self._probe_evicted(probe_dev)
        ok_r = ok_v[layout.positions]
        needs_r = needs_v[layout.positions]
        if all_ok != bool(np.all(ok_r | needs_r)):
            _MESH_VERDICT_MISMATCH.inc()
        return ok_r, needs_r, all_ok

    def _settle_partial(self, ticket, layout: _ShardLayout, ok_v, needs_v, bad):
        """Partial settlement: keep the good shards' verdicts, re-dispatch
        only the failed shards' real lanes. all_ok=None tells the verdict
        accounting to recompute from the assembled lanes (the psum scalar
        saw the faulted shards)."""
        cap = layout.shard_size - 1
        lane_shard = np.arange(layout.n, dtype=np.int64) // cap
        bad_keys = np.fromiter(bad.keys(), dtype=np.int64, count=len(bad))
        bad_mask = np.isin(lane_shard, bad_keys)
        ok_r = np.zeros(layout.n, dtype=bool)
        needs_r = np.zeros(layout.n, dtype=bool)
        good = ~bad_mask
        ok_r[good] = ok_v[layout.positions[good]]
        needs_r[good] = needs_v[layout.positions[good]]
        k = int(bad_mask.sum())
        if k:
            rows = layout.positions[bad_mask]
            sub = unpack_lanes(ticket.args[0][rows])[:-1]
            ok_b, needs_b = self._redispatch_lanes(sub, k)
            ok_r[bad_mask] = ok_b
            needs_r[bad_mask] = needs_b
        return ok_r, needs_r, None

    def _check_shards(self, ok_np, needs_np, cnts_np, wsums_np,
                      layout: _ShardLayout, elapsed: float):
        """Validate each shard's verdict slice independently.

        Returns `(ok, needs, bad)` where ok/needs are padded bool buffers
        holding the surviving shards' validated slices and `bad` maps
        shard index -> failure reason. Check order is deliberate:
        structural validation, then the per-shard checksum (so a
        single-lane flip always convicts as "checksum" — the chaos
        sweep's hard criterion), then the shard's rotating sentinel.
        """
        shard = layout.shard_size
        ok_v = np.zeros(layout.padded, dtype=bool)
        needs_v = np.zeros(layout.padded, dtype=bool)
        bad = {}
        for s in range(layout.n_shards):
            site = f"mesh.shard.{s}"
            sl = slice(s * shard, (s + 1) * shard)
            try:
                _faults.maybe_raise(site)
                delay = _faults.shard_delay(site)
                # Convict on per-SHARD lag only (today the harness's
                # simulated delay; device completion events on real
                # hardware). Whole-dispatch slowness — compile stalls, a
                # loaded host — is the in-flight ticket deadline's job:
                # folding it in here would convict all shards at once on
                # a slow machine with no fault present.
                if (
                    layout.deadline_armed
                    and delay > 0.0
                    and elapsed + delay > self._shard_deadline_s
                ):
                    _guards.GUARD_ANOMALIES.inc(site=site, reason="deadline")
                    bad[s] = "deadline"
                    continue
                ok_s = _guards.validate_verdict(
                    _faults.corrupt_verdict(site, ok_np[sl]), shard, site
                )
                needs_s = _guards.validate_verdict(needs_np[sl], shard, site)
                _guards.check_checksum(
                    (int(cnts_np[s]), int(wsums_np[s])), ok_s, site
                )
                layout.ssets[s].check(ok_s, needs_s, site)
            except _guards.VerdictAnomaly as exc:
                bad[s] = exc.reason
            except _faults.InjectedDeviceLoss:
                bad[s] = "device-loss"
            except _faults.InjectedTimeout:
                bad[s] = "timeout"
            except Exception:
                bad[s] = "dispatch"
            else:
                ok_v[sl] = ok_s
                needs_v[sl] = needs_s
        return ok_v, needs_v, bad

    # --- shard re-dispatch ---------------------------------------------

    def _redispatch_lanes(self, sub, k: int):
        """Re-answer `k` lanes whose shard failed settle: surviving mesh
        first, then the single-device XLA rung, then fail closed to the
        host oracle (lanes come back needs_host=True, so the settle layer
        resolves them exactly — a shard fault never yields an ACCEPT)."""
        for target in ("mesh", "xla"):
            try:
                if target == "mesh":
                    out = self._redispatch_mesh(sub, k)
                else:
                    out = self._redispatch_xla(sub, k)
            except Exception:
                out = None
            if out is not None:
                _MESH_REDISPATCH_LANES.inc(k, level=target)
                return out
        _MESH_REDISPATCH_LANES.inc(k, level="host")
        _guards.CONTAINED.inc(site=self._SITE)
        _guards.HOST_EXACT_LANES.inc(k)
        return np.zeros(k, dtype=bool), np.ones(k, dtype=bool)

    def _redispatch_mesh(self, sub, k: int):
        """One synchronous dispatch of the failed lanes over the current
        (possibly rebuilt) mesh, re-guarded shard-by-shard; None when the
        mesh cannot answer cleanly (caller falls to the next rung)."""
        laid = self._build_layout(sub, k, self._pad(k))
        if laid is None:
            return None
        (packed,), layout = laid
        self._note_mesh_dispatch(layout)
        ok_np, needs_np, _all_ok, cnts, wsums = self._unpack_settled(
            settle_array(self._run_step(packed)), layout
        )
        ok_v, needs_v, bad = self._check_shards(
            ok_np, needs_np, cnts, wsums, layout, 0.0
        )
        if bad:
            return None
        return ok_v[layout.positions], needs_v[layout.positions]

    def _redispatch_xla(self, sub, k: int):
        """Single-device re-answer of the failed lanes, guarded by a
        fresh contiguous sentinel set + the global verdict checksum."""
        padded = self._pad(k)
        packed, sset = self._pack_ticket(sub, k, padded)
        ok, _needs = self._settle_packed(
            self._run_level(packed, k, "xla"), padded, sset
        )
        return ok[:k], np.zeros(k, dtype=bool)

    # --- elastic mesh: eviction + re-promotion -------------------------

    def _evict_device(self, dev_id: str) -> None:
        """Convict one device: shrink the mesh to the survivors and
        re-jit the sharded step. In-flight layouts from the old epoch
        settle on the single-device rung (epoch check at relaunch)."""
        self._shard_ladder.evict(dev_id)
        _MESH_EVICTIONS.inc(device=dev_id)
        self._rebuild_mesh()

    def _rebuild_mesh(self) -> None:
        healthy = set(self._shard_ladder.healthy())
        devs = [d for d in self._all_devices if str(d.id) in healthy]
        self._mesh_epoch += 1
        self._install_mesh(make_mesh(axis=self._axis, devices=devs))

    def _probe_evicted(self, dev_id: str) -> None:
        """Known-answer re-promotion probe for an evicted device; a clean
        probe re-admits it (and re-jits the step over the grown mesh), a
        failed one leaves it quarantined for the next nomination."""
        try:
            ok = self._probe_device(dev_id)
        except Exception:
            ok = False
        if ok:
            self._shard_ladder.repromote(dev_id)
            _MESH_REPROMOTIONS.inc(device=dev_id)
            self._rebuild_mesh()

    def _probe_device(self, dev_id: str) -> bool:
        """Run an all-sentinel batch pinned to `dev_id`; True iff every
        known answer comes back right (the mesh analogue of the rung
        ladder's re-promotion probe — same idea, device-targeted)."""
        _faults.maybe_raise("mesh.probe")
        dev = next(
            (d for d in self._all_devices if str(d.id) == dev_id), None
        )
        if dev is None:
            return False
        size = 8
        packed = pad_rows(size)
        sset = _guards.install_sentinels_at(
            _lane_views(packed)[:-1], [0, 1, 2, 3], rotation=0
        )
        if sset is None:
            return False
        program = _packed_program("xla")  # runs where its argument lies
        raw = settle_array(program(jax.device_put(packed, dev)))
        ok_np, _needs, _tail = split_result(raw, 1, CHECKSUM_TAIL)
        ok = _guards.validate_verdict(ok_np, size, "mesh.probe")
        try:
            sset.check(ok, None, "mesh.probe")
        except _guards.VerdictAnomaly:
            return False
        return True

    # --- verdict accounting --------------------------------------------

    def _note_device_verdict(self, all_ok, ok, needs, count: int) -> None:
        """AND a settled chunk into the block verdict. `all_ok` is the
        psum collective's replicated scalar for fully-clean mesh
        dispatches; for partially-settled or quarantined (single-device)
        dispatches it is recomputed from the per-lane buffer with the
        same semantics (deferred lanes excluded — the host fixup ANDs
        their verdicts in via `_fixup_failed`). Accounting happens at
        settle, never dispatch, so retried or contained chunks cannot
        double-count."""
        if all_ok is None:
            lanes_ok = ok[:count]
            if needs is not None:
                lanes_ok = lanes_ok | needs[:count]
            all_ok = bool(np.all(lanes_ok))
        self._verdict_acc = self._verdict_acc and bool(all_ok)
        self._dispatched += count

    def _note_host_lanes(self, results: np.ndarray) -> None:
        self._verdict_acc = self._verdict_acc and bool(np.all(results))
        self._dispatched += len(results)

    def verify_checks_with_verdict(self, checks: Sequence[SigCheck]):
        """(per-check results, block-level all-ok).

        The all-ok verdict of device-dispatched lanes comes from the psum
        AND-reduction inside the sharded step (the collective barrier), not
        a host re-reduction; lanes rejected host-side before dispatch
        (structural parse failures) AND into the verdict via the dispatched
        count, and host-resolved exceptional deferrals AND in via
        `_fixup_failed`.
        """
        self._verdict_acc = True
        self._dispatched = 0
        self._fixup_failed = False
        try:
            res = self.verify_checks(checks)
            return res, (
                self._verdict_acc
                and self._dispatched == len(checks)
                and not self._fixup_failed
            )
        finally:
            # A raising verify_checks must not poison the NEXT verdict:
            # settle whatever is still in flight (those tickets' verdict
            # callbacks land in the accumulators being reset) and clear
            # the accounting either way.
            self._inflight.drain()
            self._verdict_acc = True
            self._dispatched = 0
            self._fixup_failed = False
