"""Real over padded lanes dispatched by the timed connects."""


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "connect":
        return None
    padded = sum(x["consensus_dispatch_padded_lanes_total"] for x in d["deltas"])
    if not padded:
        return None
    return sum(x["consensus_dispatch_lanes_total"] for x in d["deltas"]) / padded * 100.0
