"""From a profiler trace to numbers: busy union, idle share, kernel time
by name, idle gaps by what the host was inside.

`load_xplane` turns the `.xplane.pb` that `jax.profiler` writes into plain
lists; `reduce` is a pure function of those lists, checked in
`tests/test_tracered.py` on a trace recorded on a TPU v5e in PR 24.

What the trace looks like (one chip, jax 0.9.0, looked at by hand in
PR 24): a plane `/device:TPU:0` whose line `XLA Ops` holds one event per
device operation (the Pallas call, fusions, copies) and whose line `XLA
Modules` holds one event per launched program, named `jit_<fn>(<hash>)`;
a plane `/host:CPU` with one line per thread, where a
`jax.profiler.TraceAnnotation` appears under its own name. All planes
share one clock, in nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.trace_window"
ANNOTATION_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """{"device": {plane: {line: [Event]}}, "host": {line: [Event]}}. Host
    lines keep only this benchmark's own annotations, which is all the
    reduction reads and keeps a recorded trace small."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                    if ev.name.startswith(ANNOTATION_PREFIX)
                ]
                if evs:
                    host.setdefault(line.name, []).extend(evs)
    return {"device": device, "host": host}


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint, sorted cover of half-open [start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def _window(host: Dict[str, List[Event]]) -> Tuple[int, int]:
    for evs in host.values():
        for name, s, d in evs:
            if name == WINDOW_ANNOTATION:
                return s, s + d
    raise ValueError(f"the trace holds no {WINDOW_ANNOTATION!r} annotation")


def _inside(host: Dict[str, List[Event]], t: int) -> str:
    """The innermost annotation of this benchmark that covers instant `t`."""
    best: Optional[Event] = None
    for evs in host.values():
        for ev in evs:
            if ev[0] != WINDOW_ANNOTATION and ev[1] <= t < ev[1] + ev[2]:
                if best is None or ev[2] < best[2]:
                    best = ev
    return best[0] if best else "outside any benchmark call"


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle seconds over the traced window (averaged over the
    device planes), device time by operation and by program name, the
    longest idle gaps by host annotation, and the device time that falls
    inside each kind of annotation lying wholly in the window."""
    lo, hi = _window(trace["host"])
    window_s = (hi - lo) / 1e9
    busy_s: List[float] = []
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for lines in trace["device"].values():
        evs = _clip(lines.get(OPS_LINE, []), lo, hi)
        cover = union([(s, s + d) for _, s, d in evs])
        busy_s.append(sum(e - s for s, e in cover) / 1e9)
        for name, _, d in evs:
            ops[name] = ops.get(name, 0.0) + d / 1e9
        for name, s, d in lines.get(MODULES_LINE, []):
            if s >= lo and s + d <= hi:
                modules[name] = modules.get(name, 0.0) + d / 1e9
        edge = lo
        for s, e in cover + [(hi, hi)]:
            if s > edge:
                where = _inside(trace["host"], (edge + s) // 2)
                gaps[where] = gaps.get(where, 0.0) + (s - edge) / 1e9
            edge = max(edge, e)
    n = max(1, len(busy_s))
    # Device time inside each kind of benchmark call: the caller waits for
    # its result, so the device work of a call lies inside its annotation.
    spans: Dict[str, List[Tuple[int, int]]] = {}
    for evs in trace["host"].values():
        for name, s, d in evs:
            if name != WINDOW_ANNOTATION and s >= lo and s + d <= hi:
                spans.setdefault(name, []).append((s, s + d))
    within: Dict[str, dict] = {}
    for name, ivs in spans.items():
        entry = {"count": len(ivs), "span_s": sum(b - a for a, b in ivs) / 1e9,
                 "busy_s": 0.0, "ops": {}, "modules": {}}
        for lines in trace["device"].values():
            for a, b in ivs:
                cover = union([(max(s, a), min(s + d, b)) for _, s, d in lines.get(OPS_LINE, [])])
                entry["busy_s"] += sum(y - x for x, y in cover) / 1e9 / n
            for key, line in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                for ev_name, s, d in lines.get(line, []):
                    got = sum(
                        max(0, min(s + d, b) - max(s, a)) for a, b in ivs
                        if a < s + d and s < b
                    )
                    if got:
                        entry[key][ev_name] = entry[key].get(ev_name, 0.0) + got / 1e9 / n
        within[name] = entry

    def ranked(d: Dict[str, float]) -> List[List]:
        return [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": window_s,
        "busy_s": sum(busy_s) / n,
        "chips": len(busy_s),
        "device_ops": ranked(ops),
        "modules": {k: v / n for k, v in modules.items()},
        "idle_gaps": ranked(gaps),
        "within": within,
    }


def seconds_matching(by_name: Dict[str, float], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in by_name.items() if rx.search(k))
