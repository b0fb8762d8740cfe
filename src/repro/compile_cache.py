"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, the examples, the benchmark scripts)
call ``enable_compile_cache()`` once, before their first compile, so a
second run of the same programs reads them back instead of compiling.

``JAX_COMPILATION_CACHE_DIR``, when set, decides the directory and JAX
reads it itself.  Otherwise the cache goes to ``.jax_cache/`` at the
checkout's root (listed in ``.gitignore``): a fixed path, because the
path is part of the cache key — a directory named after a temp file, a
pid or a time would never hit.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the serving path's many small ones (ring
    # gathers, ingest steps, parameter init) add up on a cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
