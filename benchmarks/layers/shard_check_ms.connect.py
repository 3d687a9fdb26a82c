"""`verifier.phases` `shard_check`, median per connect: what the mesh
verifier adds to a settle on the host once the verdict buffer is there, the
per-shard checksums and the deferral mask fetched, every shard's slice held
to its checksum and its sentinel, the lanes gathered back to caller order.
It lies inside `sync` or `backpressure`, whichever settled the ticket. A
program or a verifier without the phase has nothing to read."""

from benchmarks.layers._phases import median_ms


def read(ctx):
    reports = ctx["driver"].get("phases") or []
    if not any("shard_check" in rep for rep in reports):
        return None
    return median_ms(ctx, ("shard_check",))
