"""Where the persistent XLA compilation cache lives.

The verify kernels are large traced programs (one Pallas shape compiles
for tens of seconds), so every process after the first should load them
from disk. One rule, applied by the backend at import and by the test
suite through this same function:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this code
  sets no directory — the operator (or a sealed machine's harness) has
  placed the cache.
- unset: the cache goes to `DEFAULT_DIR`, one fixed path inside the
  checkout derived from the package's own location. Never ``~``, a temp
  name, a pid or a time: a directory that moves never hits.

`configure()` also splits a set-up by JAX's own events (`jax.monitoring`):
`consensus_compile_seconds_total{stage}` sums what JAX reports for `trace`
(a function traced to a jaxpr), `lower` (the jaxpr lowered to an MLIR
module) and `backend` (the backend's compile, or the whole cache look-up
and load where the persistent cache hits). JAX reports these nested (a
traced function traces the jitted functions it calls, and runs eager ops
that compile), so each stage is credited its own time only, less what was
reported inside it on the same thread: the three stages tile, and their
sum is at most the wall time. `cache_load` is the cache retrieval alone,
as JAX reports it (inside `backend`, not taken out of it);
`consensus_compile_cache_total{result}` counts the persistent cache's
`hit`s and the `miss`es it wrote an entry for. The listeners run only when
JAX traces or compiles: nothing on a warm launch.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import jax

from ..obs import counter as _obs_counter

__all__ = ["DEFAULT_DIR", "ENV_VAR", "configure"]

_COMPILE_SECONDS = _obs_counter(
    "consensus_compile_seconds_total",
    "seconds JAX reported tracing, lowering and compiling (or loading "
    "from the persistent cache), by stage",
    ("stage",),
)
_COMPILE_CACHE = _obs_counter(
    "consensus_compile_cache_total",
    "persistent compilation cache look-ups that hit, and misses whose "
    "compile was written to it",
    ("result",),
)
# JAX times these three with one context manager, which reports a scalar
# (the start) on entry and the duration on exit: a stack a thread.
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_RESULT_OF = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_listening = False
_nested = threading.local()  # .inside: seconds reported inside each open stage


def _on_start(event: str, _value, **_kw) -> None:
    if event in _STAGE_OF:
        if not hasattr(_nested, "inside"):
            _nested.inside = []
        _nested.inside.append(0.0)


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    stage = _STAGE_OF.get(event)
    if stage is None:
        if event == _CACHE_LOAD:
            _COMPILE_SECONDS.inc(duration_secs, stage="cache_load")
        return
    inside = getattr(_nested, "inside", None)
    own = duration_secs - (inside.pop() if inside else 0.0)
    if inside:
        inside[-1] += duration_secs
    _COMPILE_SECONDS.inc(max(own, 0.0), stage=stage)


def _on_event(event: str, **_kw) -> None:
    result = _RESULT_OF.get(event)
    if result is not None:
        _COMPILE_CACHE.inc(result=result)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def configure() -> Optional[str]:
    """Apply the rule above; returns the directory this code set, or None
    when the environment variable placed it. Idempotent."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_scalar_listener(_on_start)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
