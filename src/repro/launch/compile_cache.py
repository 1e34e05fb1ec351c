"""Persistent XLA compilation cache for the program's entry points.

A cold process compiles every prefill and decode bucket of a model, at
seconds per program at published widths.  ``enable_compile_cache()``
lets the next process load them instead.  Entry points (``chip_smoke.py``,
``repro.launch.serve``, the benchmark mains) call it once at start-up;
library imports and tests never do.
"""
from __future__ import annotations

import os

import jax

# <repo root>/.jax_cache: a fixed path, because the path is part of what
# makes a later process find the entries again
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it (with the
    environment's other cache settings), and nothing is set here."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
        # keep every program, not only those over JAX's 1 s default: most
        # of a cold start is ~100 step programs of a fraction of a second
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
