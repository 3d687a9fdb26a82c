"""Real lanes a shard over the real lanes a shard can hold, over the
window's mesh dispatches: the mean of `consensus_mesh_shard_lanes` (one
observation a shard a dispatch) over the driver's `shard_capacity` (a
chunk's rows a shard, less the sentinel). Every shard runs its whole slice
whatever it holds, so this is the share of a chip's kernel time spent on
lanes somebody asked about. A driver without a mesh has nothing to read."""

from benchmarks.harness import counters


def read(ctx):
    d = ctx["driver"]
    mesh = d.get("mesh")
    if not mesh or not mesh.get("shard_capacity"):
        return None
    mean = counters.histogram_mean(d["counters_before"], d["counters_after"],
                                   "consensus_mesh_shard_lanes")
    return None if mean is None else mean / mesh["shard_capacity"] * 100.0
