"""Where the persistent XLA compilation cache lives.

Entry points (``chip_smoke.py`` and the ``repro.launch`` CLIs) call
:func:`enable_compile_cache` once at start-up; library modules never
touch the cache on import.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing here overrides it.  Otherwise the cache
goes to a fixed directory inside the checkout, so the next run finds
it again; a directory named after a temporary file, a pid or the time
would never be found again.

:func:`lowerings` counts the process's jit cache misses, so a caller can
tell whether a stretch of work compiled anything.
"""
from __future__ import annotations

import os
import pathlib
import threading

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/utils/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
# Fires once per jit cache miss, before the persistent cache is asked, so
# a program loaded from that cache counts as well.
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class _LoweringCounter:
    """JAX's monitoring listeners are process-wide, so is this count."""

    def __init__(self):
        self.n = 0
        self.listening = False
        self.lock = threading.Lock()

    def __call__(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == LOWERING_EVENT:
            with self.lock:
                self.n += 1


_COUNTER = _LoweringCounter()


def lowerings() -> int:
    """Programs lowered in this process since the first call (which
    registers the one listener)."""
    with _COUNTER.lock:
        if not _COUNTER.listening:
            jax.monitoring.register_event_duration_secs_listener(_COUNTER)
            _COUNTER.listening = True
        return _COUNTER.n
