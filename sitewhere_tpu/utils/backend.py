"""What every entry point that initialises JAX does first: place the
persistent compile cache, and say once which device it got.

A chip belongs to one process at a time, so these run in the process
that does the work — never in a launcher that goes on to start a child
that needs the chip.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    `JAX_COMPILATION_CACHE_DIR` set → JAX reads it itself and nothing is
    set in code. Otherwise `<checkout>/.jax_cache`, derived from this
    package's own location: the directory is part of the cache key, so
    it must not move between runs (no tempfile, pid or clock)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> tuple[str, str, int]:
    """(platform, device_kind, device count) as JAX reports them — the
    first call initialises the backend, and so takes the chip."""
    import jax

    devices = jax.devices()
    return devices[0].platform, devices[0].device_kind, len(devices)


def device_memory_bytes():
    """What one local device can hold, as its runtime reports it; None
    where it reports nothing (the CPU)."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")
