"""Persistent XLA compilation cache.

The analysis step compiles once per (mesh, batch geometry, sketch
geometry); a fresh process pays that compile again unless the persistent
cache is on.  Entry points (CLI, bench.py, bench_suite.py, chip_smoke.py)
call :func:`enable_persistent_cache` before first compile; libraries never
touch global JAX config themselves.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself and
  this module sets no other, so whoever runs the program places it.
- otherwise: one fixed directory inside the checkout (:data:`CACHE_DIR`,
  gitignored).  The path is part of what makes a later process hit the
  cache, so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os

#: the checkout's own cache directory, used when the environment names none
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> str | None:
    """Turn JAX's on-disk compilation cache on; return its dir (or None).

    Safe to call multiple times and before/after jax import.  CPU-only
    runs (``JAX_PLATFORMS=cpu``: the tests and CPU rehearsals) skip the
    cache: XLA:CPU re-loads its AOT result with pseudo machine features
    (+prefer-no-scatter, ...) and logs a possible-SIGILL error on every
    hit, and on some jaxlib builds the reloaded executable computes WRONG
    values (observed: corrupted HLL registers when test workers shared a
    cache dir).  An unwritable directory degrades to no caching: the
    cache is an optimization, never a requirement.
    """
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError:
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    # cache even fast compiles: a run builds several step programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
