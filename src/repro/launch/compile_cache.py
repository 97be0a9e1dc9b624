"""JAX's persistent compilation cache, switched on by entry points.

Library code never touches it: a script calls :func:`enable` first thing in
its ``__main__`` block.  ``$JAX_COMPILATION_CACHE_DIR``, when set, is left
exactly as JAX reads it; otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (the path is part of the cache key, so it must not
move between runs).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Every compiled program is cached, however quick its compile: a cold
    process otherwise recompiles the many small kernels and steps of a fit.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
