"""`verifier.phases` `shard_layout`, median per connect: what the mesh
verifier adds to a launch on the host, the chunk's lanes copied shard-major
into fresh buffers and a known-answer sentinel installed a shard. It lies
inside `dispatch` (`launch_ms.connect`). A program or a verifier without
the phase has nothing to read."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    reports = ctx["driver"].get("phases") or []
    if not any("shard_layout" in rep for rep in reports):
        return None
    return median_ms(ctx, ("shard_layout",))
