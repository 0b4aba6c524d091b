"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once at start-up; importing this module
changes nothing. The directory is fixed per checkout, so a later run of the
same checkout finds what an earlier run compiled.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_ENV_VAR", "enable_compile_cache"]

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: this file lives at <checkout>/src/repro/
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else
    ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get(CACHE_ENV_VAR) or _CHECKOUT_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    return path
