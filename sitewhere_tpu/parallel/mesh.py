"""Mesh construction + sharding helpers (SPMD foundation).

Axis convention (the scaling-book recipe: pick a mesh, annotate
shardings, let XLA insert collectives):

- `data`: batch-parallel axis. Training batches shard here; gradient
  allreduce rides ICI automatically (psum inserted by XLA under pjit).
- `model`: tensor/tenant-parallel axis. v1 uses it for per-tenant stacked
  params (tenant shards, config 4); TFT/GNN tensor sharding lands on the
  same axis later so the mesh shape is stable across models.

Multi-host: `jax.distributed.initialize` is the entry (DCN between
slices); within a process the same helpers work on any device set,
including the CPU host-platform mesh used by tests and the driver's
`dryrun_multichip` [task contract].
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(data: Optional[int] = None, model: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a (data, model) mesh over `devices` (default: all)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data is None:
        data = n // model
    if data * model != n:
        raise ValueError(f"mesh {data}x{model} != {n} devices")
    arr = np.asarray(devices).reshape(data, model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def mesh_from_spec(spec: Optional[dict]) -> Optional[Mesh]:
    """Build the serving mesh from a `{data: D, model: M}` config spec
    over this process's devices — exactly that shape, or an error.

    Only an empty spec means no mesh. `data` omitted takes every device
    the `model` axis divides. A spec that asks for more devices than the
    process has raises `ValueError` naming both: a mesh that quietly
    shrank (or vanished) would let a "mesh on" run measure some other
    configuration. More devices than D×M uses the first D×M of them (an
    explicit spec is a budget, not a floor)."""
    if not spec:
        return None
    model = max(int(spec.get("model") or 1), 1)
    devices = jax.devices()
    n = len(devices)
    data = int(spec.get("data") or 0) or n // model
    if data < 1 or data * model > n:
        raise ValueError(
            f"scoring mesh spec {spec} does not fit: this process has "
            f"{n} {devices[0].platform} device(s)")
    return make_mesh(data=data, model=model,
                     devices=devices[:data * model])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard the leading (batch) dim over `data`, replicate the rest."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def tenant_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard the leading (tenant) dim over `model`."""
    return NamedSharding(mesh, P(MODEL_AXIS, *([None] * (ndim - 1))))


def megabatch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Sharding for the pooled `[T_cap, B, ...]` megabatch inputs:
    tenant rows over `model` (co-sharded with the stacked params and
    rings), batch columns over `data`. One definition shared by the
    stacked rings (scoring/ring.py, scoring/stream.py) and the param
    stack's query path so the dispatch inputs can never be placed
    differently from the state they update."""
    return NamedSharding(
        mesh, P(MODEL_AXIS, DATA_AXIS, *([None] * (ndim - 2))))


def megabatch_placer(mesh: Optional[Mesh]):
    """`place(leaf)` for megabatch dispatch inputs — `jnp.asarray` when
    there is no mesh (the single-device stacked dispatch), the sharded
    device_put otherwise."""
    import jax.numpy as jnp

    if mesh is None:
        return jnp.asarray
    return lambda leaf: jax.device_put(leaf, megabatch_sharding(mesh,
                                                                leaf.ndim))


def tenant_placer(mesh: Optional[Mesh]):
    """`place(leaf)` for tenant-stacked state: device_put with the
    leading (tenant) axis sharded over `model`, or plain device_put when
    there is no mesh. Shared by the stacked rings (scoring/ring.py,
    scoring/stream.py) so their placement can't diverge."""
    if mesh is None:
        return jax.device_put
    return lambda leaf: jax.device_put(leaf, tenant_sharding(mesh, leaf.ndim))


def shard_batch(mesh: Mesh, *arrays: jax.Array | np.ndarray):
    """Pad each array's leading dim to a multiple of the data axis and
    place it sharded. Returns (arrays..., original_n)."""
    d = mesh.shape[DATA_AXIS]
    out = []
    n = arrays[0].shape[0]
    padded = ((n + d - 1) // d) * d
    for a in arrays:
        if padded != n:
            pad_width = [(0, padded - n)] + [(0, 0)] * (a.ndim - 1)
            a = np.pad(np.asarray(a), pad_width)
        out.append(jax.device_put(a, batch_sharding(mesh, a.ndim)))
    return (*out, n)
