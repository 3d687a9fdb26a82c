"""Named kernel-region annotation for device-time attribution.

Every consensus kernel executes under a ``jax.named_scope`` whose name
carries the ``region:`` prefix.  The scope is pure metadata: it adds no
ops to the traced program, so the interval prover, the exactness
prover, and the A/B bit-identity harness see byte-identical jaxprs.
What it *does* do is stamp every equation's ``source_info.name_stack``
(and, on real hardware, every XLA op's metadata) with the region name,
which is how a profiler trace of the chip names the kernel's ops — and
what the host-lint annotation-coverage rule (`analysis/host_lint.py`)
checks so new kernels can't land anonymous.

This module deliberately lives in ``ops/`` (not ``obs/``): kernel code
must never import the observability layer.  It has no dependencies
beyond a lazy ``jax`` import.

Region names are stable identifiers — the benchmark's trace reduction
finds the verify kernel by `region_verify_tiles`, so renaming one
changes what a per-layer metric reads.
"""

from __future__ import annotations

import functools
from contextlib import contextmanager

# Prefix distinguishing consensus kernel regions from incidental
# jit/scan scope frames in a name stack or an XLA trace event.
REGION_PREFIX = "region:"


def region_name(name: str) -> str:
    """The fully-qualified scope name for a region."""
    return REGION_PREFIX + name


@contextmanager
def region_scope(name: str):
    """Inline form: ``with region_scope("point_decode"): ...``.

    Legal both under trace and eagerly, so host seams like settle can
    use it unconditionally: under trace it extends the name stack. A
    host seam shows in a profiler capture through the `obs` span it runs
    in (`verifier.sync` at the settle seam), not through this scope.
    """
    import jax

    with jax.named_scope(region_name(name)):
        yield


def named_region(name: str):
    """Decorator: run the wrapped callable under a kernel region scope.

    >>> @named_region("fe_mul")
    ... def fe_mul(a, b): ...
    """

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import jax

            with jax.named_scope(region_name(name)):
                return fn(*args, **kwargs)

        wrapper.__consensus_region__ = name
        return wrapper

    return deco


def extract_regions(scope_name: str) -> list:
    """Every region frame in a scope/name-stack string, outermost first.

    A name stack renders as ``/``-joined frames, e.g.
    ``jit(step)/region:point_decode/region:fe_mul`` -> the op belongs to
    leaf region ``fe_mul`` within phase ``point_decode``.
    """
    out = []
    idx = scope_name.find(REGION_PREFIX)
    while idx >= 0:
        tail = scope_name[idx + len(REGION_PREFIX):]
        for sep in ("/", '"', "'", ";", ",", " "):
            cut = tail.find(sep)
            if cut >= 0:
                tail = tail[:cut]
        if tail:
            out.append(tail)
        idx = scope_name.find(REGION_PREFIX, idx + len(REGION_PREFIX))
    return out


def extract_region(scope_name: str) -> str | None:
    """The region in a scope/name-stack string, or None.

    Name stacks render as ``/``-joined frames (``jit(f)/region:fe_mul``)
    and trace-event names may embed the scope arbitrarily; the *last*
    region frame wins so the innermost annotation is the one charged —
    which is what makes ``fe_mul`` vs ``fe_mul_onehot`` A/B-attributable
    inside a larger ``scalar_mult`` region.
    """
    idx = scope_name.rfind(REGION_PREFIX)
    if idx < 0:
        return None
    tail = scope_name[idx + len(REGION_PREFIX):]
    for sep in ("/", '"', "'", ";", ",", " "):
        cut = tail.find(sep)
        if cut >= 0:
            tail = tail[:cut]
    return tail or None
