"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself; the code then sets no other), else the fixed
``<checkout>/.jax_cache`` resolved from this package's own location.
Entry points (``chip_smoke.py``, ``bench.py``) call
``enable_compile_cache()`` first thing, before anything compiles.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
