"""JAX's persistent compilation cache, at one fixed place.

A cold process compiles every kernel and jitted step it runs, which on a
TPU is a large share of a short run.  Processes that share the cache
directory skip that work.  The cache's key includes its path, so the
directory must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads the variable itself, and nothing else is set
here), otherwise ``<repo>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory.  Call before the first compilation.  Every
    program is cached, however quick its compile: the data plane's
    kernels each compile in about a second, below JAX's default floor."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
