"""Kilobytes the native interpreter fed to SHA-256 for ECDSA message
digests an input of the block, over the timed connects:
`consensus_sighash_bytes_total`, every kind, over the window, over inputs
x connects, over 1,000. A legacy digest hashes the whole transaction with
the other inputs' scripts blanked, so a transaction of 5,569 inputs reads
228.404 an input (the plain reference's own sum, `harness/sighashref.py`,
over its inputs) where a block of small transactions reads a few tenths. A
program without the counter has nothing to read."""

from benchmarks.harness import counters

_NAME = "consensus_sighash_bytes_total"


def read(ctx):
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not before or not after or _NAME not in after:
        return None
    verified = d["n_inputs"] * len(d["walls_s"])
    if not verified:
        return None
    return counters.rose(before, after, _NAME) / verified / 1000.0
