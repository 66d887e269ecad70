"""Process start-up settings for programs that run on the chip.

Nothing here runs at import: entry points (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache`` first thing, so a
library import never changes global JAX state.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "REPO_ROOT", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout this package was imported from (``src/repro/..``)
REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set, and no other directory
    is configured. Otherwise the cache lives at ``<repo>/.jax_cache``: a
    fixed path, because a cache whose directory moves from run to run
    never hits. Every compile is cached, however quick (kernels compile
    in about a second, under JAX's default one-second floor).
    """
    path = os.environ.get(CACHE_ENV) or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
