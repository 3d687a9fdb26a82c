"""Consensus telemetry: metrics registry + span tracing + exposition.

The reference crate ships no tracing at all (SURVEY §5); its only
instrument here was the ad-hoc `Phases` wall-clock timer. This package is
the production observability layer the ROADMAP north-star requires:
attribution across the host→device boundary (host parse vs limb pack vs
XLA dispatch vs readback, sigcache hits vs deferred TPU resolves) with
zero external dependencies.

Four pieces:

- ``metrics`` — a process-global, thread-safe registry of counters,
  gauges and fixed-bucket histograms, all label-aware. Every layer of the
  verify pipeline registers its metrics at import time; `snapshot()` is a
  plain dict, cheap to diff across runs.
- ``spans`` — nestable context-manager spans with monotonic timestamps.
  Every span aggregates into the registry
  (`consensus_span_duration_seconds{span=...}`); when a JSONL sink is
  attached each span additionally emits one JSON line (trace mode). With
  no sink attached the cost is two `perf_counter` reads plus one locked
  histogram update — cheap enough to leave on by default.
- ``exposition`` — Prometheus-text and JSON renderings of a snapshot,
  plus snapshot validation/diff helpers for the CLI
  (`scripts/consensus_stats.py`) and the CI `obs-smoke` artifact.
- ``flight`` — the black-box flight recorder: a bounded ring of recent
  resilience events/spans/metric deltas, dumped redacted +
  provenance-stamped on conviction (quarantine, checksum mismatch,
  chaos conviction, explicit CLI flag). Disarmed by default; the hot
  path costs one global read.

Design constraint (hard): nothing in this package is ever imported by —
or traced into — device kernel code. Instrumentation is host-side only,
so the jaxpr determinism gate (`analysis/`) and every registered kernel
jaxpr are untouched by telemetry. Conversely this is the ONE
place in the tree allowed to read clocks: the host AST lint rejects
direct `time.perf_counter()` timing in `models/` and `crypto/` so all
timing flows through spans.

Metric name catalogue and span names: README "Observability".
"""

from .metrics import (
    MetricsRegistry,
    counter,
    gauge,
    get_registry,
    histogram,
)
from .spans import (
    JsonlSink,
    Span,
    add_sink,
    current_span_id,
    current_trace,
    monotonic,
    remove_sink,
    span,
    trace_context,
)
from . import flight

__all__ = [
    "JsonlSink",
    "flight",
    "MetricsRegistry",
    "Span",
    "add_sink",
    "counter",
    "current_span_id",
    "current_trace",
    "gauge",
    "get_registry",
    "histogram",
    "monotonic",
    "remove_sink",
    "span",
    "trace_context",
]
