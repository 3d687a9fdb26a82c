"""Batch / block driver: what no phase names in a streamed block. The gap
between two successive results of a pass minus every phase `verifier.phases`
timed in it (`sync` included; on one chip no phase nests in another);
median. The driver keeps a phase record a result and a gap between two, so
a pass's first record (the stretch before the first result) is left out;
None where the two lists do not line up that way."""

from benchmarks.harness.stats import median


def read(ctx):
    d = ctx["driver"]
    if d.get("kind") != "stream" or not d["phases"]:
        return None
    n, gaps, reports = d["n_blocks"], d["block_gaps_s"], d["phases"]
    passes = len(reports) // n
    if n < 2 or len(reports) != passes * n or len(gaps) != passes * (n - 1):
        return None
    between = [rep for k, rep in enumerate(reports) if k % n]
    return median([g - sum(rep.values()) for g, rep in zip(gaps, between, strict=True)]) * 1000.0
