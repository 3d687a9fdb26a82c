"""Curve checks (real lanes) dispatched an input of the block, over the
timed connects: `consensus_dispatch_lanes_total` over inputs. A 1-of-20
CHECKMULTISIG signed by the key the walk tries last reads 20."""


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "connect" or not d["deltas"] or not d.get("n_inputs"):
        return None
    lanes = sum(x["consensus_dispatch_lanes_total"] for x in d["deltas"])
    return lanes / (d["n_inputs"] * len(d["deltas"]))
