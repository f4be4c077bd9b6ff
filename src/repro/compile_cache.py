"""JAX persistent compilation cache, kept at one fixed place per checkout.

Entry points that compile the serving programs (``chip_smoke.py``,
``examples/serve_anytime.py``, ``benchmarks/run.py``) call
``use_compile_cache`` before their first compile. A directory named by
``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; otherwise the cache
lives at ``<checkout>/.cache/jax_compile``. The path is part of every
cache key, so it never derives from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "jax_compile"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    Every compiled program is cached, however quick its compile: the
    serving path is a handful of programs whose sum is what a rerun saves.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
