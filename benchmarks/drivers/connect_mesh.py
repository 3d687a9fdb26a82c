"""The `connect_sigops` driver on a mesh of chips: the four-chip cell.

**A cell on four chips** (the worked example `benchmarks/README.md` has not
got yet; that file is a `benchmark` PR's to edit). It is data and one thin
driver: `"chips": 4` in the cell and the configuration; a `verifier` group
with a `kind` (`sharded`) and the `mesh` size beside the arguments the
one-chip configuration gives; `backend` `mesh`, the label every dispatch
of `parallel/mesh.ShardedSecpVerifier` carries in
`consensus_dispatch_total`; a `kernel` (`pallas`, `xla` in the rehearsal),
the label every one must carry in `consensus_mesh_dispatch_total`; and a
traffic file that names this driver. The driver is `connect_sigops`'s loop,
timing, corrupted block and comparisons, unchanged, with `make_verifier`
here in the place of `harness/cell.make_verifier`, which knows one class.
`harness/tracered.reduce` averages over the device planes, so every
`device_trace` reader reads a chip's mean without an edit; the readers that
sum lanes (`kernel_gops.connect`) read the mesh's aggregate.

On top of `connect_sigops`'s rules, `correct` needs of the window:

- `consensus_mesh_dispatch_total{kernel=<configuration's kernel>}` risen by
  ceil(pairings / lane_capacity) a connect (ten at the cell's size) and
  under no other `kernel`: every dispatch sharded, the Pallas kernel on
  every shard (`shard_kernel` answers `xla` for a shard the 512-lane tile
  does not divide, and the dispatch is still labelled `mesh`);
- no rise of `consensus_mesh_shard_failures_total`,
  `consensus_mesh_evictions_total`, `consensus_mesh_redispatch_lanes_total`,
  `consensus_mesh_repromotions_total` or
  `consensus_mesh_verdict_mismatch_total` (the replicated psum verdict of a
  dispatch against the AND of its settled lanes), and the mesh still at
  the configuration's size when the window closes.

The controls are the harness's own: `fault-plan` flips one lane of a
materialized verdict buffer, which the shard's checksum convicts (a shard
failure, a re-dispatch, a guard anomaly); `lane-flip` inverts one settled
chunk where the verifier hands it to the driver, which fills the signature
cache with failed pairings; `truth-shift` moves the corrupted block's victim.

A program whose mesh verifier lacks the `kernel` label (any before this
driver's PR) cannot be held to the first rule: the module refuses to load
there, at once, so that the cell fails cleanly and is not half-measured.
"""

from __future__ import annotations

import sys
from contextlib import contextmanager

from ..harness import cell, counters
from . import connect_sigops

_DISPATCHES = "consensus_mesh_dispatch_total"
# What a sound window leaves where it found it.
_STILL = (
    "consensus_mesh_shard_failures_total",
    "consensus_mesh_evictions_total",
    "consensus_mesh_redispatch_lanes_total",
    "consensus_mesh_repromotions_total",
    "consensus_mesh_verdict_mismatch_total",
)
_SHARD_LANES = "consensus_mesh_shard_lanes"


def _require_kernel_label() -> None:
    from bitcoinconsensus_tpu.obs import get_registry
    from bitcoinconsensus_tpu.parallel import mesh  # noqa: F401  (registers the mesh's metrics)

    metric = get_registry().get(_DISPATCHES)
    if metric is None or "kernel" not in metric.labelnames:
        print(f"refusing to run: this program's {_DISPATCHES} has no `kernel` label; "
              "its mesh dispatches cannot be held to the Pallas kernel", file=sys.stderr)
        raise SystemExit(2)


_require_kernel_label()


def make_verifier(config: dict):
    """`harness/cell.make_verifier`'s successor for a `verifier` group of
    `kind` `sharded`: `mesh` devices in JAX's own order, the other
    arguments to the verifier as they are."""
    args = dict(config["verifier"])
    kind = args.pop("kind")
    if kind != "sharded":
        raise ValueError(f"this driver builds a sharded verifier, not {kind!r}")
    from bitcoinconsensus_tpu.parallel.mesh import ShardedSecpVerifier, make_mesh

    return ShardedSecpVerifier(mesh=make_mesh(int(args.pop("mesh"))), **args)


@contextmanager
def _built_by(builder):
    """`connect.Driver.setup` builds its verifier through
    `harness/cell.make_verifier`; for its duration that name is `builder`."""
    harness_own = cell.make_verifier
    cell.make_verifier = builder
    try:
        yield
    finally:
        cell.make_verifier = harness_own


class Driver(connect_sigops.Driver):
    def setup(self) -> None:
        with _built_by(make_verifier):
            super().setup()
        self.shards = int(self.verifier.mesh.devices.size)
        capacity = self.verifier.lane_capacity
        self.dispatches_a_connect = -(-int(self.data["pairings"]) // capacity)

    def _mesh_problems(self) -> list:
        before, after = self.watch.before, self.watch.after
        out = []
        by_kernel = counters.rose_by_label(before, after, _DISPATCHES, "kernel")
        want = {self.config["kernel"]: float(self.dispatches_a_connect * len(self.walls))}
        if by_kernel != want:
            out.append(f"mesh dispatches by kernel {by_kernel}, not {want} "
                       f"({self.dispatches_a_connect} a connect)")
        for name in _STILL:
            rose = counters.rose(before, after, name)
            if rose:
                out.append(f"{name} +{rose:g}")
        size = int(self.verifier.mesh.devices.size)
        if size != int(self.config["verifier"]["mesh"]):
            out.append(f"the mesh ended the window {size} wide")
        return out

    def verify(self) -> dict:
        out = super().verify()
        problems = self._mesh_problems()
        out["problems"].extend(problems)
        out["correct"] = out["correct"] and not problems
        return out

    def layer_context(self) -> dict:
        ctx = super().layer_context()
        chunk_rows = self.verifier.pad(self.verifier.lane_capacity)
        ctx["mesh"] = {"shards": self.shards,
                       "shard_capacity": chunk_rows // self.shards - 1}
        return ctx

    def detail(self) -> dict:
        before, after = self.watch.before, self.watch.after
        return {**super().detail(), "mesh": {
            "shards": self.shards,
            "dispatches_by_kernel": counters.rose_by_label(before, after, _DISPATCHES, "kernel"),
            "shard_lanes_mean": counters.histogram_mean(before, after, _SHARD_LANES),
        }}
