"""Batch / block driver: what no phase names. Connect wall minus the
`outer_secs` of every phase in that connect's `verifier.phases` report (the
seconds a phase was the outermost open one on the caller's thread, so a
phase nested in another is not counted twice); median per connect. None on
a program whose reports carry no `outer_secs`."""

from benchmarks.harness.stats import median


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "connect" or not d["phases"]:
        return None
    if not all("outer_secs" in v for rep in d["phases"] for v in rep.values()):
        return None
    named = [sum(v["outer_secs"] for v in rep.values()) for rep in d["phases"]]
    return median([w - p for w, p in zip(d["walls_s"], named, strict=True)]) * 1000.0
