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
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["DEFAULT_DIR", "ENV_VAR", "configure"]

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
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
