"""Performance observatory: ticket phase timelines, overlap efficiency,
roofline/cost accounting, and provenance-stamped perf reports.

The ROADMAP's perf arc (kill the 208 ms link wait, raise VPU utilization)
needs its numbers measured *continuously*, inside the async pipeline —
not reconstructed by hand from one-off scripts. Three layers live here:

- **PhaseTimeline** — rides every `resilience/inflight.py` Ticket.
  Monotonic stamps (through the sanctioned `obs` clock — the host AST
  lint's clock rule stays intact) at submit / prepare / launch /
  first-poll / settle-start / settle-end, plus per-shard stamps from the
  mesh settle seam. Finalizing a timeline feeds the
  `consensus_pipeline_phase_seconds{phase=…}` histograms and the derived
  `consensus_pipeline_overlap_efficiency` gauge: the fraction of a
  ticket's wire time (launch → settled) the host spent *not* waiting —
  the continuous successor to the one-off "208 ms of 282.7 ms is link
  wait" measurement. Dispatch-path hot code never touches more than a
  dict store per stamp; `BITCOINCONSENSUS_TPU_PERF_TIMELINE=0` disarms
  timelines entirely (a shared no-op instance — the A/B knob for the
  <1 % overhead budget).

- **Roofline/cost accounting** — the traced-jaxpr integer-op walk that
  `scripts/kernel_roofline.py` pioneered, as a reusable library
  (`walk_jaxpr`, `while_trips`, `kernel_report`), plus
  `Compiled.cost_analysis()` where the installed jax exposes it. Scripts
  stay thin wrappers.

- **Provenance + reports** — `provenance()` stamps every perf artifact
  with backend/device/versions/git-rev, `comparable()` decides whether
  two artifacts may be compared at all (the BENCH_r06 "CPU container
  numbers are NOT comparable to TPU v5e" footgun, closed structurally),
  and `compare_reports()` is the regression gate
  `scripts/consensus_perf.py --check` and CI's perf-smoke job run.

Nothing here is ever traced into a device kernel; jax/numpy imports are
lazy so the telemetry package stays dependency-light at import time.
"""

from __future__ import annotations

import os
import platform as _platform
import subprocess
import sys
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from .metrics import gauge, get_registry, histogram
from .spans import monotonic

__all__ = [
    "NULL_TIMELINE",
    "PEAK_INT_OPS_V5E",
    "PhaseTimeline",
    "compare_reports",
    "comparable",
    "cost_analysis",
    "kernel_report",
    "new_timeline",
    "overlap_efficiency",
    "phase_report",
    "provenance",
    "register_kernel",
    "reset_overlap_window",
    "registered_kernels",
    "set_enabled",
    "timed_best",
    "timeline_enabled",
    "walk_jaxpr",
    "while_trips",
]

_PHASE_SECONDS = histogram(
    "consensus_pipeline_phase_seconds",
    "per-ticket pipeline phase durations (README: Performance "
    "observatory phase names)",
    ("phase",),
)
_OVERLAP = gauge(
    "consensus_pipeline_overlap_efficiency",
    "fraction of recent tickets' wire time hidden by host-side work "
    "(1.0 = the link wait is fully overlapped, 0.0 = fully exposed)",
)

# (histogram phase label, start stamp, end stamp). "inflight" is the
# overlap window: the host came back to poll the ticket only after this
# long — time the device spent working while the host did something else.
_PHASE_EDGES: Tuple[Tuple[str, str, str], ...] = (
    ("prepare", "submit", "prepare"),
    ("launch", "prepare", "launch"),
    ("inflight", "launch", "first_poll"),
    ("settle", "settle_start", "settle_end"),
    ("total", "submit", "settle_end"),
)

# Overlap gauge window: recent (hidden, wire) second pairs; the gauge is
# sum(hidden)/sum(wire), so long tickets weigh proportionally.
_OVERLAP_WINDOW = 256
_overlap_lock = threading.Lock()
_overlap_win: deque = deque(maxlen=_OVERLAP_WINDOW)

_enabled = os.environ.get(
    "BITCOINCONSENSUS_TPU_PERF_TIMELINE", ""
) not in ("0", "off")


def set_enabled(flag: bool) -> None:
    """Arm/disarm phase timelines process-wide (the A/B overhead knob).
    Tickets already carrying a live timeline finish it; new dispatches
    get the shared no-op instance while disarmed."""
    global _enabled
    _enabled = bool(flag)


def timeline_enabled() -> bool:
    return _enabled


def reset_overlap_window() -> None:
    """Drop accumulated overlap samples (test isolation; the metrics
    registry's `reset()` does not reach this module-level window)."""
    with _overlap_lock:
        _overlap_win.clear()


def _note_overlap(hidden: float, wire: float) -> None:
    with _overlap_lock:
        _overlap_win.append((hidden, wire))
        h = sum(x for x, _ in _overlap_win)
        w = sum(y for _, y in _overlap_win)
    if w > 0.0:
        _OVERLAP.set(h / w)


class PhaseTimeline:
    """Monotonic stamp sheet for one in-flight dispatch ticket.

    The queue stamps the lifecycle edges; `finalize()` (idempotent, at
    settle) turns them into phase histogram observations and one overlap
    sample. `trace` carries the submitting request's trace id across the
    worker-thread boundary for post-hoc JSONL correlation.
    """

    __slots__ = ("stamps", "shards", "trace", "_done")

    def __init__(self, trace: Optional[int] = None):
        self.stamps: Dict[str, float] = {}
        self.shards: List[Tuple[int, float]] = []
        self.trace = trace
        self._done = False

    def stamp(self, name: str) -> None:
        """Record `name` at now; re-stamping overwrites (a relaunch after
        a retry moves the launch edge — the settled attempt is the one
        attributed)."""
        self.stamps[name] = monotonic()

    def stamp_once(self, name: str) -> None:
        """Record `name` only if unseen (first_poll must survive
        re-settles)."""
        if name not in self.stamps:
            self.stamps[name] = monotonic()

    def stamp_shard(self, idx: int) -> None:
        """Record completion of shard `idx`'s settle-side check."""
        self.shards.append((idx, monotonic()))

    def phase_seconds(self) -> Dict[str, float]:
        """Derived per-phase durations (only edges with both stamps)."""
        t = self.stamps
        out: Dict[str, float] = {}
        for phase, a, b in _PHASE_EDGES:
            if a in t and b in t and t[b] >= t[a]:
                out[phase] = t[b] - t[a]
        return out

    def finalize(self) -> None:
        """Feed the registry once: phase histograms, per-shard check
        durations, and the overlap-efficiency sample."""
        if self._done:
            return
        self._done = True
        for phase, dt in self.phase_seconds().items():
            _PHASE_SECONDS.observe(dt, phase=phase)
        t = self.stamps
        start = t.get("settle_start")
        if self.shards and start is not None:
            prev = start
            for _idx, ts in self.shards:
                if ts >= prev:
                    _PHASE_SECONDS.observe(ts - prev, phase="shard_check")
                prev = ts
        launch = t.get("launch")
        poll = t.get("first_poll")
        end = t.get("settle_end")
        if launch is not None and poll is not None and end is not None:
            wire = end - launch
            if wire > 0.0:
                _note_overlap(min(max(poll - launch, 0.0), wire), wire)


class _NullTimeline:
    """Shared disarmed timeline: every hook a no-op, zero per-ticket
    allocation. `trace` reads as None; there is nothing to set."""

    __slots__ = ()
    trace = None

    def stamp(self, name: str) -> None:
        pass

    def stamp_once(self, name: str) -> None:
        pass

    def stamp_shard(self, idx: int) -> None:
        pass

    def phase_seconds(self) -> Dict[str, float]:
        return {}

    def finalize(self) -> None:
        pass


NULL_TIMELINE = _NullTimeline()


def new_timeline(trace: Optional[int] = None):
    """A live PhaseTimeline, or the shared no-op when disarmed."""
    if not _enabled:
        return NULL_TIMELINE
    return PhaseTimeline(trace)


# ---------------------------------------------------------------------------
# Registry readbacks (report side).


def phase_report() -> Dict[str, dict]:
    """Per-phase {count, mean_s, total_s} from the pipeline histograms —
    the report block `scripts/consensus_perf.py` emits and gates on."""
    h = get_registry().get("consensus_pipeline_phase_seconds")
    out: Dict[str, dict] = {}
    if h is None:
        return out
    for s in h._samples():
        if s["count"]:
            out[s["labels"]["phase"]] = {
                "count": s["count"],
                "mean_s": s["sum"] / s["count"],
                "total_s": s["sum"],
            }
    return out


def overlap_efficiency() -> Optional[float]:
    """Current overlap-efficiency gauge value, or None before any
    settled ticket fed the window."""
    g = get_registry().get("consensus_pipeline_overlap_efficiency")
    if g is None or not g._samples():
        return None
    return float(g.value())


# ---------------------------------------------------------------------------
# Roofline / cost accounting (shared by kernel_roofline + consensus_perf).

# v5e VPU int32 ceiling: (8, 128) vector unit x 4 ALUs at ~0.94 GHz.
PEAK_INT_OPS_V5E = 3.85e12

ARITH = {
    "add", "sub", "mul", "and", "or", "xor", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "select_n", "eq", "ne",
    "lt", "le", "gt", "ge", "min", "max", "neg", "abs", "rem", "not",
    "convert_element_type", "broadcast_in_dim", "concatenate", "iota",
    "reduce_and", "reduce_or", "reduce_sum", "reduce_min", "reduce_max",
}
# Conservative split: data movement / shape ops are NOT compute but still
# occupy the VPU pipeline; counted separately.
MOVE = {"convert_element_type", "broadcast_in_dim", "concatenate", "iota"}


def while_trips(eqn) -> int:
    """Trip count of a lowered `fori_loop` (a `while` whose carry init
    holds the static upper bound as a scalar int literal — take the
    largest such literal; exact for every fori in the verify kernel:
    window loop, G loop, the _sqr_n chains)."""
    from jax.extend.core import Literal

    trips = 1
    for v in eqn.invars:
        if isinstance(v, Literal) and getattr(v.aval, "shape", None) == ():
            try:
                trips = max(trips, int(v.val))
            except (TypeError, ValueError):
                pass
    return trips


def walk_jaxpr(jaxpr) -> Tuple[int, int]:
    """Sum (compute_ops, move_ops) element counts over a jaxpr: every
    arithmetic/logic/select/compare primitive's output elements — the
    int32 work the VPU actually executes (loads/stores and MXU dots
    excluded). Recurses into pjit/call bodies, `while` (fori trip counts
    via `while_trips`), `scan` (`length`), and any param carrying a
    jaxpr (pallas_call bodies included)."""
    import numpy as np

    comp = move = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "while":
            c, m = walk_jaxpr(eqn.params["body_jaxpr"].jaxpr)
            t = while_trips(eqn)
            comp += c * t
            move += m * t
            continue
        if prim == "scan":
            c, m = walk_jaxpr(eqn.params["jaxpr"].jaxpr)
            comp += c * eqn.params["length"]
            move += m * eqn.params["length"]
            continue
        recursed = False
        for p in eqn.params.values():
            # ClosedJaxpr (.jaxpr) or raw Jaxpr (.eqns) — pallas_call
            # carries the latter.
            sub = getattr(p, "jaxpr", p if hasattr(p, "eqns") else None)
            if sub is not None:
                c, m = walk_jaxpr(sub)
                comp += c
                move += m
                recursed = True
        if recursed:
            continue
        outs = sum(int(np.prod(vv.aval.shape)) for vv in eqn.outvars)
        if prim in MOVE:
            move += outs
        elif prim in ARITH:
            comp += outs
    return comp, move


def _block(x) -> None:
    """Wait for every array leaf of `x` (timing helper; report side only
    — dispatch-path code settles through resilience/inflight)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(x):
        wait = getattr(leaf, "block_until_ready", None)
        if wait is not None:
            wait()


def timed_best(fn: Callable[[], Any], reps: int = 5):
    """(best_s, median_s, walls) over `reps` synchronized calls of `fn`
    — min-of-N approximates the uncontended kernel."""
    walls = []
    for _ in range(max(1, int(reps))):
        t0 = monotonic()
        _block(fn())
        walls.append(monotonic() - t0)
    return min(walls), sorted(walls)[len(walls) // 2], walls


def cost_analysis(fn: Callable, *args) -> Dict[str, float]:
    """XLA's own cost model for `fn(*args)` where the installed jax
    exposes `Compiled.cost_analysis()`; {} when unavailable. Numeric
    entries only (the raw dict carries non-JSON values on some
    backends)."""
    try:
        import jax

        compiled = jax.jit(fn).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return {
            str(k): float(v)
            for k, v in dict(ca).items()
            if isinstance(v, (int, float))
        }
    except Exception:
        return {}


_KERNELS: Dict[str, Callable[[], tuple]] = {}


def register_kernel(name: str, make: Callable[[], tuple]) -> None:
    """Register a kernel for the perf report. `make()` -> (run, run_args)
    or (run, run_args, trace_fn, trace_args) — built lazily so
    registration never compiles anything."""
    _KERNELS[name] = make


def registered_kernels() -> Dict[str, Callable[[], tuple]]:
    return dict(_KERNELS)


def kernel_report(
    name: str,
    run: Callable,
    run_args: tuple,
    trace_fn: Optional[Callable] = None,
    trace_args: Optional[tuple] = None,
    reps: int = 5,
    peak: float = PEAK_INT_OPS_V5E,
    with_cost_analysis: bool = True,
) -> dict:
    """Machine-readable roofline for one kernel.

    Op count from the TRACED program (`trace_fn(*trace_args)`, defaults
    to the timed call — pass a one-tile interpret trace when the grid
    repeats one program), timing from min-of-`reps` synchronized calls
    of `run(*run_args)`, ceiling from `peak`. Lanes = leading dim of the
    first argument of each side.
    """
    import jax

    trace_fn = run if trace_fn is None else trace_fn
    trace_args = run_args if trace_args is None else trace_args
    closed = jax.make_jaxpr(trace_fn)(*trace_args)
    comp, move = walk_jaxpr(closed.jaxpr)
    trace_lanes = int(trace_args[0].shape[0])
    lanes = int(run_args[0].shape[0])
    ops_per_lane = comp / trace_lanes
    move_per_lane = move / trace_lanes
    _block(run(*run_args))  # warm the compile; timing below excludes it
    best, median, _walls = timed_best(lambda: run(*run_args), reps=reps)
    lanes_per_s = lanes / best
    achieved = ops_per_lane * lanes_per_s
    out = {
        "kernel": name,
        "lanes": lanes,
        "trace_lanes": trace_lanes,
        "reps": int(reps),
        "best_ms": round(best * 1000, 3),
        "median_ms": round(median * 1000, 3),
        "lanes_per_sec_best": round(lanes_per_s, 1),
        "int_ops_per_lane": round(ops_per_lane, 1),
        "move_ops_per_lane": round(move_per_lane, 1),
        "achieved_int_ops_per_sec": f"{achieved:.3e}",
        "vpu_peak_int_ops_per_sec": f"{peak:.3e}",
        "vpu_utilization_pct": round(100 * achieved / peak, 2),
    }
    if with_cost_analysis:
        ca = cost_analysis(trace_fn, *trace_args)
        if ca:
            out["xla_cost_analysis"] = ca
    return out


# ---------------------------------------------------------------------------
# Provenance + the regression gate.

# Provenance keys that must MATCH for two perf artifacts to be compared
# at all. git rev and versions are recorded but deliberately not part of
# the comparability key — the gate exists precisely to compare across
# revisions on the same hardware class.
COMPARABLE_KEYS = ("platform", "device_kind")


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


def provenance(cmd: Optional[str] = None) -> dict:
    """Where a perf number came from: backend platform + device kind,
    jax/jaxlib/python versions, git revision, and the producing command.
    Stamped into every artifact this repo's bench writers emit."""
    doc = {
        "platform": "unavailable",
        "device_kind": "unavailable",
        "device_count": 0,
        "jax": "unavailable",
        "jaxlib": "unavailable",
        "python": sys.version.split()[0],
        "git_rev": _git_rev(),
        "cmd": " ".join(sys.argv) if cmd is None else cmd,
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
            kind = devs[0].device_kind
            if doc["platform"] == "cpu":
                # A bare "cpu" would make every CPU box "comparable" and
                # flap the throughput gate across machines; qualify it so
                # the gate only bites on matched hardware.
                kind = (
                    f"{kind}/{_platform.machine()}"
                    f"-{os.cpu_count() or 0}c"
                )
            doc["device_kind"] = kind
            doc["device_count"] = len(devs)
    except Exception:
        pass
    return doc


def comparable(a: dict, b: dict) -> Tuple[bool, str]:
    """Whether two provenance blocks describe comparable hardware; the
    reason string names the first mismatched key when not."""
    for k in COMPARABLE_KEYS:
        if a.get(k) != b.get(k):
            return False, f"{k}: {a.get(k)!r} vs {b.get(k)!r}"
    return True, ""


def compare_reports(
    baseline: dict,
    report: dict,
    tolerance: float = 0.5,
    abs_floor_s: float = 1e-3,
) -> Optional[List[str]]:
    """Regression-gate a perf report against a checked-in baseline.

    Returns None when the two are not comparable (provenance mismatch —
    a container run never fails a TPU baseline), else the list of
    regression descriptions (empty = pass). A phase regresses when its
    mean grew BOTH by more than `tolerance` (relative) and by more than
    `abs_floor_s` (absolute) — microsecond-scale phases don't flap the
    gate on scheduler noise. Throughput regresses on relative drop alone.
    """
    ok, _why = comparable(
        baseline.get("provenance", {}), report.get("provenance", {})
    )
    if not ok:
        return None
    problems: List[str] = []
    old_tp = (baseline.get("workload") or {}).get("verifies_per_sec")
    new_tp = (report.get("workload") or {}).get("verifies_per_sec")
    if old_tp and new_tp and new_tp < old_tp * (1.0 - tolerance):
        problems.append(
            f"throughput regression: {new_tp:.1f} verifies/s vs baseline "
            f"{old_tp:.1f} (tolerance {tolerance:.0%})"
        )
    old_ph = baseline.get("phases") or {}
    new_ph = report.get("phases") or {}
    for phase, old in sorted(old_ph.items()):
        new = new_ph.get(phase)
        if new is None:
            continue
        o, n = old.get("mean_s"), new.get("mean_s")
        if o is None or n is None:
            continue
        if n > o * (1.0 + tolerance) and n - o > abs_floor_s:
            problems.append(
                f"phase '{phase}' regression: mean {n * 1e3:.2f} ms vs "
                f"baseline {o * 1e3:.2f} ms (tolerance {tolerance:.0%}, "
                f"floor {abs_floor_s * 1e3:.0f} ms)"
            )
    return problems
