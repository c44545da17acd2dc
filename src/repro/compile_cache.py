"""JAX's persistent compilation cache, for the entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable_compile_cache`
before their first compile, so that the processes of one machine share
compiled programs. Nothing calls it on package import: the tests compile
without a persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# fixed, at the root of the checkout: the cache is only found again under
# the same path, so no part of it may come from a temp name, a pid or a time
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """The cache directory: ``JAX_COMPILATION_CACHE_DIR`` where it is set,
    else ``.jax_cache/`` at the root of the checkout."""
    return environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory. JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set no other
    directory is configured."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
