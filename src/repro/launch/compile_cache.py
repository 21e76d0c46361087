"""Where the launchers keep JAX's persistent compilation cache.

Every launcher and ``chip_smoke.py`` calls ``enable_compile_cache()``
once, before its first compile. With ``JAX_COMPILATION_CACHE_DIR`` set,
JAX reads that directory itself and nothing here overrides it.
Otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout: a fixed path, because the directory is part of what a later
run must find again (never a temporary name, a pid or a time).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` — this file is
#: ``<checkout>/src/repro/launch/compile_cache.py``.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Every compile is kept, however short: a serving warmup is many
    sub-second Mosaic kernel and program compiles, each under JAX's
    default one-second threshold for caching.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
