"""JAX's persistent compilation cache for the repo's scripts.

Scripts (`chip_smoke.py`, `benchmarks/noc_bench.py`, the examples) call
`enable_compile_cache()` once at start-up, before anything compiles.  It
is never called on library import or from the tests.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable
itself and no other directory is set here.  Otherwise the cache lives at
the fixed path ``<repo>/.jax_cache``: a directory derived from a
temporary name, a process id or the time would never be found again by
the next run.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every compiled program is cached, however quickly it compiled: a
    session builds many small jitted steps, and a run on a fresh machine
    pays for each of them.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
