"""Where JAX's persistent compilation cache lives for the launchers.

The launchers (``launch/count.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`use_compile_cache` once at startup; no library module sets a
cache when it is imported.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

#: ``<repo>/.jax_cache``.  Fixed: the directory is part of each entry's
#: key, so a temporary or per-process path would never hit.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here; otherwise the cache goes to
    ``REPO_CACHE_DIR``.  Every program is cached, however quick its
    compile: a counter compiles in under JAX's default one-second
    threshold, and a run compiles hundreds of such programs."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
