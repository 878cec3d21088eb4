"""JAX's persistent compilation cache, placed once per process.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` before their first compile; importing the
library never turns the cache on, so tests compile as they always have.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache", "DEFAULT_CACHE_DIR"]

# <checkout>/.jax_cache: a fixed path, because the path is part of each entry's key
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turns the persistent compilation cache on and returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
