"""Batch / block driver: the resolve round's signature-cache work,
`verifier.phases` `sig_probe` (the salted probe of a round's new checks;
skipped on an empty cache) + `sig_insert` (a settled chunk's verdicts
stored and its successes inserted), median per connect. None on a program
that has neither phase."""

from benchmarks.layers._phases import median_ms

PHASES = ("sig_probe", "sig_insert")


def read(ctx):
    reports = ctx["driver"].get("phases") or []
    if not any(n in rep for rep in reports for n in PHASES):
        return None
    return median_ms(ctx, PHASES)
