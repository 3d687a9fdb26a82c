"""Wire front end: a request's time on the ingress loop outside the verify
server: mean of `consensus_ingress_seconds{stage="decode"}` (its frame's
last byte read to `submit` returned) + mean of `{stage="respond"}` (the
worker resolving it to its verdict frame written and drained: the hop from
the worker's thread to the loop under one GIL) over the window."""

from benchmarks.layers._spans import labelled

INGRESS_SECONDS = "consensus_ingress_seconds"


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "serve":
        return None
    means = []
    for stage in ("decode", "respond"):
        got = labelled(d["counters_before"], d["counters_after"], INGRESS_SECONDS, "stage", stage)
        if got is None or not got[1]:
            return None
        means.append(got[0] / got[1])
    return sum(means) * 1000.0
