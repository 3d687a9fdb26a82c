"""Pause the cyclic GC across a bounded, allocation-heavy driver section.

A verify_batch/connect_block pass allocates hundreds of thousands of
short-lived objects (prep records, check tuples, cache keys), which
drives CPython's generational GC into repeated full collections — and a
full collection scans the ENTIRE heap, including the multi-gigabyte
object graph a loaded JAX/jaxlib runtime keeps alive. With the collector
paused the pass pays for none of those scans.

The pause is bounded and state-restoring: reference counting still frees
the (acyclic) bulk of the churn immediately; only cycle collection is
deferred, and a young-generation sweep runs at exit so any cyclic
garbage from the section is reclaimed promptly. Nested pauses are safe
(the inner one is a no-op), and a caller who already disabled GC keeps
it disabled. BITCOINCONSENSUS_TPU_GC_PAUSE=0 turns the whole mechanism
off.
"""

from __future__ import annotations

import gc
import os
import threading
from contextlib import contextmanager, nullcontext

__all__ = ["gc_paused"]

_lock = threading.Lock()
_depth = 0
_reenable = False


@contextmanager
def gc_paused(phases=None):
    """Depth-counted across threads: concurrent verify_batch calls are a
    supported pattern (models/sigcache.py mutex contract), so the
    collector re-enables only when the LAST paused section exits.
    `phases` (the caller's `utils.profiling.Phases`, if it has one) times
    that exit's young-generation sweep as the `gc_sweep` phase."""
    global _depth, _reenable
    if os.environ.get("BITCOINCONSENSUS_TPU_GC_PAUSE", "") in ("0", "off"):
        yield
        return
    with _lock:
        if _depth == 0:
            _reenable = gc.isenabled()
            gc.disable()
        _depth += 1
    try:
        yield
    finally:
        with _lock:
            _depth -= 1
            sweep = _depth == 0 and _reenable
            if sweep:
                gc.enable()
        if sweep:
            with nullcontext() if phases is None else phases("gc_sweep"):
                gc.collect(0)  # sweep the sections' young garbage promptly
