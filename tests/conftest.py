"""Test environment: force JAX onto CPU with 8 virtual devices.

Per SURVEY.md §4 (rebuild test strategy): TPU tests run identically on CPU
via a host-platform device mesh, so sharding/pjit tests exercise real
multi-device semantics without TPU hardware. Must run before jax import.
"""

import os

# hard override, not setdefault: the tests run on the CPU whatever the
# machine has (a host with a chip sets JAX_PLATFORMS=tpu,cpu).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, (
    f"test mesh wants 8 virtual CPU devices, got {jax.devices()}")


@pytest.fixture
def run():
    """Run a coroutine on a fresh event loop (sync test driver)."""

    def _run(coro):
        return asyncio.run(coro)

    return _run
