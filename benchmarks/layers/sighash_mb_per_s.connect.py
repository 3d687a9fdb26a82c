"""Megabytes a second one worker thread of the native interpreter sustains
from building an ECDSA digest's preimage to its double hash, over the
timed connects: `consensus_sighash_bytes_total` over
`consensus_sighash_seconds_total` (thread seconds, summed over the
workers), every kind, over the window. To be read against what the SHA-256
transform alone does on a buffer of the same length (`PERF.md`): the gap
is the preimage's building. A program without the counters, or a window
that spent no time there, has nothing to read."""

from benchmarks.harness import counters

_BYTES = "consensus_sighash_bytes_total"
_SECONDS = "consensus_sighash_seconds_total"


def read(ctx):
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not before or not after:
        return None
    if _BYTES not in after or _SECONDS not in after:
        return None
    seconds = counters.rose(before, after, _SECONDS)
    if not seconds > 0:
        return None
    return counters.rose(before, after, _BYTES) / seconds / 1e6
