"""Where the persistent XLA compilation cache lives.

Entry points (``chip_smoke.py`` and the ``repro.launch`` CLIs) call
:func:`enable_compile_cache` once at start-up; library modules never
touch the cache on import.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing here overrides it.  Otherwise the cache
goes to a fixed directory inside the checkout, so the next run finds
it again; a directory named after a temporary file, a pid or the time
would never be found again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/utils/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
