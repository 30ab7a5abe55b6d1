"""Multi-host (DCN) entry: process group init + global mesh.

SURVEY.md §2.4/§5.8: within a slice, collectives ride ICI; across
slices/hosts they ride DCN. JAX's recipe — and therefore ours — is one
process per host, `jax.distributed.initialize` to form the process
group, then a SINGLE global mesh spanning every process's devices; pjit
over that mesh makes XLA place ICI collectives inside a slice and DCN
collectives across them. Nothing else in the framework changes: the
trainer/pool shard over the same `data`/`model` axes whether the mesh is
one chip, a v5e-8, or a v5p-32 multi-host job.

Environment-variable contract (mirrors the usual launcher convention):
    SWX_COORDINATOR   host:port of process 0 (e.g. "10.0.0.1:8476")
    SWX_NUM_PROCESSES total process count
    SWX_PROCESS_ID    this process's rank

Tested without hardware: two CPU processes form a global mesh over
virtual host-platform devices and train in lockstep to identical losses
(tests/test_distributed.py) — the same entry a v5p-32 job uses.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

from sitewhere_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh

logger = logging.getLogger(__name__)

_initialized = False


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           local_device_ids=None) -> bool:
    """Join (or skip joining) the multi-process group.

    Explicit args win; otherwise the SWX_* env contract is read; if
    neither names a coordinator, this is a single-process run and the
    call is a no-op returning False. Idempotent."""
    global _initialized
    if _initialized:
        return True
    coordinator_address = coordinator_address or os.environ.get(
        "SWX_COORDINATOR")
    if coordinator_address is None:
        return False
    if num_processes is None:
        num_processes = int(os.environ["SWX_NUM_PROCESSES"])
    if process_id is None:
        process_id = int(os.environ["SWX_PROCESS_ID"])
    # CPU backend: XLA ships no cross-process collectives by default —
    # device_put/psum across the process group fail with "Multiprocess
    # computations aren't implemented on the CPU backend" unless the
    # gloo transport is selected BEFORE the backend initializes. TPU/GPU
    # backends bring their own (ICI/DCN, NCCL) and must not be touched.
    platforms = os.environ.get("JAX_PLATFORMS", "") \
        or str(getattr(jax.config, "jax_platforms", None) or "")
    if "cpu" in platforms or not platforms:
        # explicit cpu, or nothing requested (a bare CPU-only host
        # resolves to cpu too): selecting gloo only configures the CPU
        # backend's collectives — accelerator backends are untouched
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)
    _initialized = True
    logger.info("joined process group: rank %d/%d via %s",
                process_id, num_processes, coordinator_address)
    return True


def make_global_mesh(data: Optional[int] = None, model: int = 1):
    """A (data, model) mesh over EVERY process's devices.

    After `initialize_distributed`, `jax.devices()` is the global device
    list in a stable order (grouped by process), so every process builds
    the identical mesh — the SPMD requirement. Local-only computation
    should keep using `make_mesh(devices=jax.local_devices())`."""
    return make_mesh(data=data, model=model, devices=jax.devices())


def process_info() -> dict:
    """Rank/size/device facts for logs and health endpoints."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "initialized": _initialized,
    }


__all__ = ["initialize_distributed", "make_global_mesh", "process_info",
           "DATA_AXIS", "MODEL_AXIS"]
