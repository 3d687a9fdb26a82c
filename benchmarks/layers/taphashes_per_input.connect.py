"""Taproot hashes the native interpreter made an input of the block, over
the timed connects: `consensus_taproot_hash_total`, every `what` (BIP 341
digests, TapLeaf, TapBranch, TapTweak), over the window, over inputs x
connects. `taproot-block.cold`: 1.75 = 0.95 digests (one a key-path input
and a lone leaf, two a 2-of-3) + 0.2 leaf hashes + 0.4 branch hashes (depth
2) + 0.2 tweak hashes, where each is made once. A program without the
counter has nothing to read."""

from benchmarks.harness import counters

_NAME = "consensus_taproot_hash_total"


def read(ctx):
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not before or not after or _NAME not in after:
        return None
    verified = d["n_inputs"] * len(d["walls_s"])
    if not verified:
        return None
    return counters.rose(before, after, _NAME) / verified
