"""JAX's persistent compilation cache for the repo's entry points.

Every entry point (``chip_smoke.py``, ``benchmarks.run``,
``benchmarks.serve_qps``) calls :func:`enable_compile_cache` before its
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing else is set.  Otherwise the cache lives at a fixed
path in the checkout, ``<repo>/.jax_cache`` (gitignored): the directory
is part of what a later process must find again, so it is never built
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
