"""Warm restarts: the jax persistent compilation cache.

``enable_persistent_cache()`` points jax at an on-disk XLA executable
cache with the thresholds opened up so every resident program qualifies
(CPU compiles are fast and small — the defaults would skip them all).
The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise one fixed directory inside the checkout, ``<repo>/.jax_cache``
(ignored by git): the path is part of what a later run must find again,
so it is never made from a temporary name, a process id or the time.

A restarted server's first request then deserializes its programs
instead of recompiling: ``repro.analysis.tracecheck`` counts the
persistent-cache hits separately (``Watch.fresh_compiles``), and the
restart subprocess test asserts the second boot pays ZERO fresh
compiles.

Call it before the first jit dispatch; it is idempotent.
"""
from __future__ import annotations

import os

from .. import CHECKOUT_ROOT

DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def default_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_persistent_cache() -> str:
    """Enable the on-disk compilation cache; returns the directory."""
    import jax

    path = default_cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache EVERY executable: CPU compiles are below the default 1 MiB /
    # 1 s thresholds, which would silently cache nothing here
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
