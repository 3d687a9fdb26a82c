"""Black-box flight recorder: bounded ring buffer + dump-on-conviction.

When the resilience machinery convicts something — a guard rejects a
verdict buffer, the degradation ladder demotes a backend, a shard gets
evicted — the interesting evidence is what happened in the seconds
*before*. This module keeps that evidence: a bounded, thread-safe ring
of recent events (resilience decisions, span completions, anything the
hook sites `record()`), plus the metric registry delta since arming.
On a trigger (quarantine, checksum mismatch, chaos conviction, explicit
CLI flag) the ring is dumped — redacted and provenance-stamped — to a
``flight_dump_<reason>_*.json`` the chaos harness and operators can
read post-mortem.

Disarmed by default: the fast
path of `record()` is a single module-global read, so the recorder
costs nothing measurable inside the <1% resilience overhead budget
until armed via ``BITCOINCONSENSUS_TPU_FLIGHT=1`` or `set_enabled()`.
Span subscription attaches a sink only while armed, so the span hot
path is untouched when disarmed.

Redaction: consensus inputs (scripts, signatures, pubkeys, message
bytes) never belong in a dump that may leave the machine. Any event
field whose key smells sensitive is replaced by ``<redacted:N bytes>``
recursively before serialization.

Dumps are count-capped per process (`MAX_DUMPS`), deliberately NOT
time-rate-limited: a chaos sweep convicting on back-to-back trials must
get a complete dump for each conviction, and a production incident
rarely needs more than the first few dumps anyway.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from collections import deque
from typing import Any, Dict, List, Optional

from .metrics import counter, gauge, get_registry
from . import exposition as _exposition
from . import spans as _spans

__all__ = [
    "CAPACITY",
    "MAX_DUMPS",
    "enabled",
    "events",
    "provenance",
    "record",
    "reset",
    "set_enabled",
    "trigger",
]

SCHEMA = "consensus-flight-v1"

# Ring capacity: large enough to hold the span/decision window around a
# conviction (a verify batch emits a handful of spans), small enough to
# bound memory and dump size.
CAPACITY = 512

# Dumps written per process before the recorder goes quiet (count cap,
# not a rate limit — see module docstring).
MAX_DUMPS = 16

_EVENTS = counter(
    "consensus_flight_events_total",
    "events accepted by the flight ring while armed, by kind",
    ("kind",),
)
_DUMPS = counter(
    "consensus_flight_dumps_total",
    "flight dumps written, by trigger reason",
    ("trigger",),
)
_ARMED_GAUGE = gauge(
    "consensus_flight_armed",
    "1 while the flight recorder is armed, else 0",
)
_ARMED_GAUGE.set(0)

# Event-field keys whose values are redacted from dumps. Substring
# match, case-insensitive: "pubkey_x", "script_sig", "msg32" all hit.
REDACT_KEYS = (
    "payload", "data", "sig", "pubkey", "pub_key", "msg", "message",
    "raw", "script", "secret", "privkey", "key_bytes", "witness",
)

_lock = threading.Lock()
_armed = os.environ.get("BITCOINCONSENSUS_TPU_FLIGHT", "0") not in (
    "0", "", "false", "no")
_ring: deque = deque(maxlen=CAPACITY)
_appended = 0  # lifetime accepted count; - len(ring) = evicted
_dumps_written = 0
_dump_seq = 0
_armed_snapshot: Optional[dict] = None
_span_sink = None


class _FlightSpanSink:
    """Span sink feeding completed spans into the ring (attached only
    while armed; `spans.add_sink` errors are already counted there)."""

    def write(self, rec: dict) -> None:
        record("span", **rec)


def enabled() -> bool:
    return _armed


def set_enabled(flag: bool) -> None:
    """Arm or disarm the recorder (idempotent).

    Arming snapshots the metric registry (dumps carry the delta since
    arming) and subscribes the span sink; disarming detaches the sink so
    the span path returns to its unobserved cost.
    """
    global _armed, _armed_snapshot, _span_sink
    with _lock:
        if flag and not _armed:
            _armed_snapshot = get_registry().snapshot()
            _span_sink = _FlightSpanSink()
            _spans.add_sink(_span_sink)
            _armed = True
            _ARMED_GAUGE.set(1)
        elif not flag and _armed:
            _armed = False
            if _span_sink is not None:
                _spans.remove_sink(_span_sink)
                _span_sink = None
            _ARMED_GAUGE.set(0)


def reset() -> None:
    """Clear ring + dump counters (test isolation helper)."""
    global _appended, _dumps_written, _dump_seq, _armed_snapshot
    with _lock:
        _ring.clear()
        _appended = 0
        _dumps_written = 0
        _dump_seq = 0
        if _armed:
            _armed_snapshot = get_registry().snapshot()


def record(kind: str, **fields) -> None:
    """Append one event to the ring. Disarmed cost: one global read."""
    if not _armed:
        return
    global _appended
    ev = {"t": _spans.monotonic(), "kind": kind}
    ev.update(fields)
    with _lock:
        _ring.append(ev)
        _appended += 1
    _EVENTS.inc(kind=kind)


def events() -> List[dict]:
    """Current ring contents, oldest first (copy)."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """Events evicted from the ring since arming/reset."""
    with _lock:
        return max(0, _appended - len(_ring))


def _redact(value: Any, key: str = "") -> Any:
    low = key.lower()
    if any(tok in low for tok in REDACT_KEYS):
        try:
            size = len(value)  # type: ignore[arg-type]
        except TypeError:
            size = 0
        return f"<redacted:{size}>"
    if isinstance(value, dict):
        return {k: _redact(v, str(k)) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_redact(v) for v in value]
    if isinstance(value, (bytes, bytearray)):
        return f"<bytes:{len(value)}>"
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    return repr(value)


def _dump_dir() -> str:
    return os.environ.get("BITCOINCONSENSUS_TPU_FLIGHT_DIR", "/tmp")


def _git_rev() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=root, capture_output=True, text=True, timeout=5,
        )
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except Exception:
        return "unknown"


def provenance() -> dict:
    """Where a dump or a record came from: backend platform and device
    kind, jax/jaxlib/python versions, git revision and the command."""
    doc = {
        "platform": "unavailable",
        "device_kind": "unavailable",
        "device_count": 0,
        "jax": "unavailable",
        "jaxlib": "unavailable",
        "python": sys.version.split()[0],
        "git_rev": _git_rev(),
        "cmd": " ".join(sys.argv),
    }
    try:
        import jax

        doc["jax"] = jax.__version__
        try:
            import jaxlib

            doc["jaxlib"] = jaxlib.__version__
        except Exception:
            pass
        doc["platform"] = jax.default_backend()
        devs = jax.devices()
        if devs:
            doc["device_kind"] = devs[0].device_kind
            doc["device_count"] = len(devs)
    except Exception:
        pass
    return doc


def trigger(reason: str, out_dir: Optional[str] = None,
            **attrs) -> Optional[str]:
    """Dump the flight ring; returns the written path (None when
    disarmed or the per-process dump cap is exhausted).

    The dump holds: the trigger reason + attrs (redacted), the full
    event window oldest-first, the count of ring-evicted events, the
    metric deltas since arming, and a provenance stamp — everything a
    post-mortem needs without re-running the workload.
    """
    global _dumps_written, _dump_seq
    if not _armed:
        return None
    with _lock:
        if _dumps_written >= MAX_DUMPS:
            return None
        _dumps_written += 1
        _dump_seq += 1
        seq = _dump_seq
        window = list(_ring)
        evicted = max(0, _appended - len(_ring))
        base_snap = _armed_snapshot or {}
    deltas = _exposition.diff_snapshots(base_snap, get_registry().snapshot())
    doc = {
        "schema": SCHEMA,
        "trigger": reason,
        "attrs": _redact(dict(attrs)),
        "provenance": provenance(),
        "events": [_redact(ev) for ev in window],
        "events_dropped": evicted,
        "metric_deltas": deltas,
    }
    out_dir = out_dir or _dump_dir()
    path = os.path.join(
        out_dir, f"flight_dump_{reason}_{os.getpid()}_{seq:03d}.json")
    try:
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True, default=repr)
            fh.write("\n")
    except OSError:
        return None
    _DUMPS.inc(trigger=reason)
    return path
