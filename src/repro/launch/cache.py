"""Persistent XLA compilation cache for the entry points.

Call :func:`enable_compile_cache` at the start of a ``main`` (never at
import time).  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps
its cache there and nothing is changed; otherwise the cache goes to
``.jax_cache/`` at the root of the checkout — a fixed path, because the
path is part of each entry's key and a moving directory never hits.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
