"""JAX's persistent compilation cache for the program's entry points.

``launch.serve.main`` and ``chip_smoke.py`` call :func:`enable_compile_cache`
before their first compile; importing any ``repro`` module never does.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX read it at import and that
  directory stands — nothing here sets another.
* unset: the cache goes to :data:`CACHE_DIR`, ``<checkout>/.jax_cache``.  The
  path is fixed (never built from a temporary name, a pid or the time),
  because it is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process and
    return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: a cold start compiles dozens of small step
    # programs, each under JAX's default one-second threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
