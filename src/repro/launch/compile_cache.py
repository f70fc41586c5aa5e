"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks.run``,
``examples/fl_simulation.py``) call :func:`enable_compile_cache` in their
``main``; nothing turns the cache on at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this module
sets nothing.  Otherwise the cache lives at the fixed ``<checkout>/
.jax_cache``: the directory is part of the cache key, so a name that
changed per run (a temporary directory, a pid, a time) would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
