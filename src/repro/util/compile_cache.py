"""Persistent JAX compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``repro.launch.infer_gnn``,
``repro.launch.infer_dist``, ``benchmarks/bench_delivery.py``) call
``enable_compile_cache()`` once, before their first compile; importing
the library never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and this sets no other directory.  Otherwise the cache
lives at ``<checkout>/.jax_cache`` — a fixed path, because the path is
part of what a later process must find again.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # kernel compiles take about a second; the default 1 s floor would
    # keep most of them out of the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]
