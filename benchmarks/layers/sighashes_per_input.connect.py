"""ECDSA message digests the native interpreter hashed an input of the
block, over the timed connects:
`consensus_sighash_total{result="computed"}` over the window, over
inputs x connects. A 1-of-20 CHECKMULTISIG interpreted in two fixpoint
rounds reads 2.0 where each round makes its signature's digest once, and 23
where every pairing of the key walk hashes it anew. A program without the
counter has nothing to read."""

from benchmarks.harness import counters

_NAME = "consensus_sighash_total"


def read(ctx):
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not before or not after or _NAME not in after:
        return None
    verified = d["n_inputs"] * len(d["walls_s"])
    if not verified:
        return None
    rose = counters.rose_by_label(before, after, _NAME, "result")
    return rose.get("computed", 0.0) / verified
