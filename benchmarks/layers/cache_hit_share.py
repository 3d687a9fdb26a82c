"""Cache probes answered from the caches, over the timed connects:
`consensus_cache_hits_total` / `consensus_cache_lookups_total`. A connect
on empty caches makes no lookup, and then there is nothing to read."""


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "connect":
        return None
    lookups = sum(x["consensus_cache_lookups_total"] for x in d["deltas"])
    if not lookups:
        return None
    return sum(x["consensus_cache_hits_total"] for x in d["deltas"]) / lookups * 100.0
