"""JAX's persistent compilation cache for the entry points.

Each entry point's ``main()`` calls :func:`enable` (never at import
time, so tests and library users keep JAX's defaults).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``,
a path taken from this file's location: the directory is part of the
cache key, so it must not move between runs (no temporary, per-process
or time-stamped name).
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
