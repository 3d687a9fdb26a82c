"""What the readers of the program's own spans share: every `obs` span
feeds `consensus_span_duration_seconds{span}`, so the seconds a span took
over the window are a difference of two registry snapshots. A program
that has no such span (or histogram label) reads None, not 0."""

from typing import Optional, Tuple

from benchmarks.harness import counters

SPAN_SECONDS = "consensus_span_duration_seconds"
BATCHES = "consensus_serving_batches_total"


def labelled(before: dict, after: dict, name: str, label: str,
             value: str) -> Optional[Tuple[float, float]]:
    """(seconds, observations) a histogram took between two snapshots under
    one label value; None where the later snapshot has no such sample."""
    def sums(snap):
        ss = [s for s in counters._samples(snap, name) if s["labels"].get(label) == value]
        return (sum(s["sum"] for s in ss), sum(s["count"] for s in ss)) if ss else None

    late = sums(after)
    if late is None:
        return None
    early = sums(before) or (0.0, 0)
    return late[0] - early[0], late[1] - early[1]


def span_seconds(ctx: dict, spans: Tuple[str, ...]) -> Optional[float]:
    """Seconds the window spent in `spans` together, over all threads;
    None where one of them took no sample."""
    d = ctx["driver"]
    got = [labelled(d["counters_before"], d["counters_after"], SPAN_SECONDS, "span", s)
           for s in spans]
    return None if any(g is None for g in got) else sum(g[0] for g in got)


def ms_per_batch(ctx: dict, plus: Tuple[str, ...], minus: Tuple[str, ...] = ()) -> Optional[float]:
    """(seconds in the spans `plus` - seconds in the spans `minus`) a
    coalesced batch of the served window, in milliseconds."""
    d = ctx["driver"]
    if d.get("kind") != "serve":
        return None
    batches = counters.rose(d["counters_before"], d["counters_after"], BATCHES)
    added, taken = span_seconds(ctx, plus), span_seconds(ctx, minus)
    if not batches or added is None or taken is None:
        return None
    return (added - taken) / batches * 1000.0
