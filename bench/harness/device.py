"""The accelerator a run stands on: found, described, measured."""
from __future__ import annotations

from typing import Dict, List


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def accelerators(chips: int) -> List:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def describe(devices: List) -> Dict:
    d = devices[0]
    return dict(platform=d.platform, kind=d.device_kind, count=len(devices))


def memory_peak_bytes(devices: List) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.utils.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``), keeping every program, however quick
    to compile, so that a second run compiles nothing."""
    import jax

    from repro.utils.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
