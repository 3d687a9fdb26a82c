"""The share of the dispatched CHECKMULTISIG lanes that Core's own walk
would have verified, over the timed connects:
`consensus_multisig_walk_pairings_total` (the pairings the cursor walk
tried in the interpretation whose verdict was returned) over
`consensus_multisig_spec_pairings_total` (the pairings pre-recorded ahead
of the walk that became checks of their own). 100 where every lane is one
the walk needs (a 1-of-20 signed by the key tried last), 19.23 for an
8-of-20 signed by the eight first-pushed keys: 20 of 104. A program
without either counter, or a window that pre-recorded nothing, has nothing
to read."""

from benchmarks.harness import counters

_WALK = "consensus_multisig_walk_pairings_total"
_SPEC = "consensus_multisig_spec_pairings_total"


def read(ctx):
    d = ctx["driver"]
    before, after = d.get("counters_before"), d.get("counters_after")
    if d.get("kind") != "connect" or not before or not after:
        return None
    if _WALK not in after or _SPEC not in after:
        return None
    spec = counters.rose(before, after, _SPEC)
    if not spec > 0:
        return None
    return counters.rose(before, after, _WALK) / spec * 100.0
