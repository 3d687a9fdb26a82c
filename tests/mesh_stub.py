"""A host stand-in for `parallel.mesh.make_sharded_step`'s program.

The mesh verifier's host side (layout, launch, per-shard settle, partial
re-dispatch, eviction) is tested in-process without compiling the sharded
program: this step answers in the program's own wire format, one packed
buffer in and one packed result out, from any function that gives a verdict
a lane. The compiled program itself runs in `tests/mesh_checks.py`'s
children, where its unpack is held to `unpack_lanes` and its result to this
packing.
"""

import jax
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from bitcoinconsensus_tpu.parallel import mesh as M
from bitcoinconsensus_tpu.resilience.guards import verdict_checksum_host


def traced_unpack(mesh):
    """The sharded program's first ops jitted alone, under the program's own
    sharding: `unpack(packed on the mesh) -> the eight arrays`, a shard's
    rows at a time."""
    axis = mesh.axis_names[0]
    return jax.jit(shard_map(
        M._unpack_lanes_traced, mesh=mesh, in_specs=P(axis, None),
        out_specs=(P(axis, None, None),) + (P(axis),) * 7,
    ))


def pack_result(ok, needs, all_ok, n_shards: int) -> np.ndarray:
    """What the sharded program returns for these verdicts: a shard's
    `ok + 2 * needs` a row, then its checksum pair and the psum verdict."""
    out = []
    for ok_s, needs_s in zip(np.split(np.asarray(ok, dtype=bool), n_shards),
                             np.split(np.asarray(needs, dtype=bool), n_shards)):
        out.append(ok_s.astype(np.int32) + 2 * needs_s.astype(np.int32))
        out.append(np.array([*verdict_checksum_host(ok_s), int(all_ok)], dtype=np.int32))
    return np.concatenate(out)


def host_step(verifier, lane_verdicts):
    """`step(packed) -> result` over `verifier`'s current mesh, with
    `lane_verdicts(fields, want_odd, parity, has_t2, neg1, neg2, valid)`
    (the kernel's signature) as the kernel, no lane deferred."""

    def step(packed):
        *lanes, live = M.unpack_lanes(np.asarray(packed))
        ok = np.asarray(lane_verdicts(*lanes), dtype=bool)
        return pack_result(ok, np.zeros_like(ok), not (live & ~ok).any(),
                           int(verifier.mesh.devices.size))

    return step
