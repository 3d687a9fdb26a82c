"""Dispatches a timed connect sends to the device (`consensus_dispatch_total`
over the connects): 1 where a block's checks fit one chunk, the number of
chunks where they do not."""


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "connect" or not d["deltas"]:
        return None
    return sum(x["consensus_dispatch_total"] for x in d["deltas"]) / len(d["deltas"])
