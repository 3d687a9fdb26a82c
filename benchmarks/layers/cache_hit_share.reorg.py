"""Batch and block driver: cache probes answered from the caches over the
timed reorganisations: `consensus_cache_hits_total` /
`consensus_cache_lookups_total`. The script cache answers for the
transactions both branches hold; a disconnect makes no lookup."""

from benchmarks.layers._reorg import summed


def read(ctx):
    hits = summed(ctx, "consensus_cache_hits_total")
    lookups = summed(ctx, "consensus_cache_lookups_total")
    if hits is None or not lookups:
        return None
    return hits / lookups * 100.0
