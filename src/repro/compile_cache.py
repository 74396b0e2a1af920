"""Persistent compile cache for the repository's entry points.

``enable_compile_cache(checkout)`` is called by ``chip_smoke.py`` and
``benchmarks/run.py`` — never at library import and never by the tests.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is configured.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the cache key includes
the directory, so a path built from a temp name, a pid or the time would
never be hit again.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str) -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
