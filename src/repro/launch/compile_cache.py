"""Persistent XLA compilation cache shared by the launchers and
``chip_smoke.py``.

The serving engine compiles one program per prefill bucket of its pow2
shape lattice plus its decode chunks and kernels; without a persistent
cache every cold process pays for all of them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/src/repro/launch/compile_cache.py -> <repo>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself and nothing here overrides it. Otherwise the cache
    lives at the fixed in-checkout path ``<repo>/.jax_cache`` (listed in
    ``.gitignore``) — never a temporary, per-process or dated name, which
    would never be hit again. Every program is cached, however quick its
    compile: the lattice is many small programs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
