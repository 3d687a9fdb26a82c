"""How late the benchmark's own client sent: sent minus due, 95th
percentile over the window's requests. A starved generator must not read
as a fast server."""

from benchmarks.harness.stats import percentile


def read(ctx):
    lag = ctx["driver"].get("lag_ms")
    return percentile(lag, 95.0) if lag else None
