"""The 95th percentile of request latency (due time to last verdict frame; a
failed, shed or unanswered request reads over any limit). A tail is made
where transactions of many inputs are cut into batches. Per-layer, not
end-to-end: with 4,000 requests a window the tail of this mix is too steep
around its 95th percentile to repeat within any bound (PERF.md, section 2)."""

from benchmarks.harness.stats import percentile


def read(ctx):
    lat = ctx["driver"].get("latency_ms")
    return percentile(lat, 95.0) if lat else None
