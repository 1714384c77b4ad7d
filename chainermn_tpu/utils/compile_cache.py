"""Where compiled programs are kept between runs.

One helper for every entry point (``chip_smoke.py``, ``bench.py``, the
example CLIs), so that processes which share compiles share one cache.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache —
    JAX reads it itself and nothing is set in code. Otherwise the cache
    is ``<checkout>/.jax_cache``: a fixed place, never a temp name, pid
    or timestamp, so the next run finds what this one compiled."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
