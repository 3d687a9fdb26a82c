"""A slice of the measured window under `jax.profiler`, for `--trace 1`."""

from __future__ import annotations

import json
import os
import shutil
from typing import Optional

from . import counters, tracered


class Tracer:
    """Starts the profiler once the window has run `start_share` of its
    length and stops it `trace_s` later; the driver polls between its
    calls. The traced slice is marked by one annotation, which the
    reduction takes as its window. With `enabled` false every call is a
    no-op."""

    def __init__(self, enabled: bool, directory: str, seconds: float,
                 start_share: float = 0.3, trace_s: float = 4.0):
        self.enabled = enabled
        self.directory = directory
        self.start_at = seconds * start_share
        self.stop_at = self.start_at + min(trace_s, seconds * 0.4)
        self._mark = None
        self.state = "idle" if enabled else "done"
        self.counters_before = self.counters_after = None

    def poll(self, elapsed: float) -> None:
        if self.state == "idle" and elapsed >= self.start_at:
            import jax

            shutil.rmtree(self.directory, ignore_errors=True)
            os.makedirs(self.directory, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the annotations are enough; Python tracing slows the host
            self.counters_before = counters.snapshot()
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self._mark = jax.profiler.TraceAnnotation(tracered.WINDOW_ANNOTATION)
            self._mark.__enter__()
            self.state = "tracing"
        elif self.state == "tracing" and elapsed >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        if self.state != "tracing":
            return
        import jax

        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.counters_after = counters.snapshot()
        self.state = "done"

    def reduced(self, keep_copy_to: Optional[str] = None) -> Optional[dict]:
        """The reduction of what was traced, or None when tracing is off.
        `keep_copy_to` (a path prefix) keeps the trace for a look by hand:
        the lists the reduction reads as JSON, and the profiler's own file
        where it is small."""
        if not self.enabled:
            return None
        self.stop()
        path = tracered.find_xplane(self.directory)
        trace = tracered.load_xplane(path)
        if keep_copy_to:
            os.makedirs(os.path.dirname(keep_copy_to) or ".", exist_ok=True)
            with open(keep_copy_to + ".json", "w") as f:
                json.dump(trace, f)
            if os.path.getsize(path) < 16 << 20:
                shutil.copy(path, keep_copy_to + ".xplane.pb")
        shutil.rmtree(self.directory, ignore_errors=True)
        out = tracered.reduce(trace)
        out["counters_before"] = self.counters_before
        out["counters_after"] = self.counters_after
        return out


def annotate(name: str):
    """A span of the benchmark's own, visible in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(tracered.ANNOTATION_PREFIX + name)
