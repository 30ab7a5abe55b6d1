"""Device-resident telemetry ring: per-device history in TPU HBM.

The TPU-first answer to SURVEY.md §7 hard part (a). The hot scoring
path never ships windows over the host→device link (what a host sync
costs on the chip's own host is not measured): per-device history lives
on device as a ring `[capacity+1, window]` (row `capacity` is a scratch
row that absorbs padding writes), and ONE jit fuses

    scatter (append new values) → gather (per-device window) → model.score

so a flush transfers only the deltas — device ids (int32) + values
(float32), 8 bytes/event — and returns the scores. State buffers are
donated, so XLA updates the ring in place with no on-device copies.

The host-side columnar `TelemetryStore` (persistence/telemetry.py) stays
the durable query/training copy; `load()` re-syncs the ring from it at
warmup or after a dispatch fault.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.utils import grow_pow2

logger = logging.getLogger(__name__)


def _append_window_score(w: int, score: Callable, out_dtype,
                         params, vals, cnt, cur, dev, v):
    """The fused update's body, one ring's (the stacked ring vmaps it
    over tenants), under the `jax.named_scope`s a profile shows its
    parts by: the append, the window gather, the model's score."""
    with jax.named_scope("ring_scatter"):
        pos = cur[dev]
        vals = vals.at[dev, pos].set(v, mode="drop")
        cur = cur.at[dev].set((pos + 1) % w, mode="drop")
        cnt = jnp.minimum(cnt.at[dev].add(1, mode="drop"), w)
    with jax.named_scope("ring_gather"):
        idx = (cur[dev][:, None] - w + jnp.arange(w)[None, :]) % w
        x = vals[dev[:, None], idx]
        valid = jnp.arange(w)[None, :] >= (w - cnt[dev])[:, None]
    with jax.named_scope("window_score"):
        scores = score(params, x, valid)
        if out_dtype is not None:
            scores = scores.astype(out_dtype)
    return vals, cnt, cur, scores


class DeviceRing:
    """Ring of one scalar channel for up to `capacity` devices, resident
    on `device` (default backend device)."""

    def __init__(self, window: int, capacity: int = 1024,
                 initial_floor: int = 1024, score_dtype=None):
        self.window = int(window)
        self.capacity = grow_pow2(int(capacity), floor=initial_floor)
        # narrow flush-path score readback (float16 halves the only
        # per-event device→host payload); settle upcasts on assignment
        self.score_dtype = jnp.dtype(score_dtype) if score_dtype else None
        self._update_score_fns: dict[tuple, Callable] = {}
        # evidence trail for the bench artifact and chip_smoke.py: None
        # = no bucket selected the fused (Pallas) scorer (model has none
        # / predicate declined), "compiled" once one did and compiled
        self.fused_status: Optional[str] = None
        self.faulted = False  # True after a failed dispatch donated state away
        self._alloc(self.capacity)

    # -- state -------------------------------------------------------------

    def _alloc(self, cap: int) -> None:
        w = self.window
        self.values = jnp.zeros((cap + 1, w), jnp.float32)
        self.count = jnp.zeros(cap + 1, jnp.int32)
        self.cursor = jnp.zeros(cap + 1, jnp.int32)

    def ensure_capacity(self, max_index: int) -> None:
        """Grow (device-side) so `max_index` is a valid device row."""
        if max_index < self.capacity:
            return
        new_cap = grow_pow2(max_index + 1, floor=self.capacity * 2)
        grow = new_cap - self.capacity
        # drop the old scratch row (its contents are garbage), zero-extend,
        # append a fresh scratch row
        self.values = jnp.pad(self.values[:-1], ((0, grow + 1), (0, 0)))
        self.count = jnp.pad(self.count[:-1], (0, grow + 1))
        self.cursor = jnp.pad(self.cursor[:-1], (0, grow + 1))
        self.capacity = new_cap

    def load(self, values: np.ndarray, count: np.ndarray,
             start: int = 0) -> None:
        """Overwrite rows `start..start+n` from host window data.

        `values[n, window]` is chronological with left padding (the
        `TelemetryStore.window` layout); `count[n]` is valid entries per
        row. Ring form places the valid suffix at positions `0..count-1`
        with the cursor pointing at the next slot.
        """
        n, w = values.shape
        assert w == self.window
        self.ensure_capacity(start + n - 1 if n else 0)
        cnt = np.minimum(count.astype(np.int32), w)
        # shift each row left by (w - cnt) so valid data sits at 0..cnt-1
        idx = (np.arange(w)[None, :] + (w - cnt)[:, None]) % w
        ring_rows = np.take_along_axis(values.astype(np.float32), idx, axis=1)
        self.values = self.values.at[start:start + n].set(ring_rows)
        self.count = self.count.at[start:start + n].set(cnt)
        self.cursor = self.cursor.at[start:start + n].set(cnt % w)
        self.faulted = False

    # -- compiled steps ----------------------------------------------------

    def _build_update_score(self, model, cap: int, bucket: int,
                            prefer_fused: bool = True) -> Callable:
        w = self.window
        out_dtype = self.score_dtype
        # the dedicated ring is never vmapped, so it may take the
        # model's fused (Pallas) scorer when one exists; the stacked
        # ring stays on `score` (lax.scan batches under vmap)
        score = (getattr(model, "score_fused", model.score)
                 if prefer_fused else model.score)

        def step(params, vals, cnt, cur, dev, v):
            return _append_window_score(w, score, out_dtype,
                                        params, vals, cnt, cur, dev, v)

        return jax.jit(step, donate_argnums=(1, 2, 3))

    def _pad(self, dev: np.ndarray, v: np.ndarray,
             bucket: int) -> tuple[np.ndarray, np.ndarray]:
        n = dev.shape[0]
        out_dev = np.full(bucket, self.capacity, np.int32)  # scratch row
        out_v = np.zeros(bucket, np.float32)
        out_dev[:n] = dev
        out_v[:n] = v
        return out_dev, out_v

    def update_and_score(self, model, params, dev: np.ndarray,
                         v: np.ndarray, bucket: int) -> jax.Array:
        """Append `v[i]` to ring row `dev[i]` (unique ids!), score every
        touched device's window; returns `[bucket]` scores on device
        (async — caller settles off-loop)."""
        key = (self.capacity, bucket)
        fn = self._update_score_fns.get(key)
        pdev, pv = self._pad(dev, v, bucket)
        if fn is None:
            from sitewhere_tpu.ops.lstm_kernel import pallas_ok

            prefer = (hasattr(model, "score_fused")
                      and pallas_ok(bucket,
                                    getattr(model.cfg, "layers", 0),
                                    getattr(model.cfg, "compute_dtype",
                                            None)))
            fn = self._build_update_score(model, self.capacity, bucket,
                                          prefer_fused=prefer)
            if prefer:
                # AOT lower+compile executes nothing, so donation
                # consumes no buffers. A selected kernel the chip's
                # compiler refuses RAISES here (warm-up keeps the
                # exception where callers read it) — there is no silent
                # rebuild on the scan. The Compiled object is kept so
                # dispatch does not compile a second time.
                fn = fn.lower(params, self.values, self.count,
                              self.cursor, pdev, pv).compile()
                self.fused_status = "compiled"
                logger.info(
                    "fused Pallas scorer compiled for bucket %d "
                    "(capacity %d) — kernel path engaged",
                    bucket, self.capacity)
            self._update_score_fns[key] = fn
        try:
            self.values, self.count, self.cursor, scores = fn(
                params, self.values, self.count, self.cursor, pdev, pv)
        except Exception:
            self.faulted = True  # donated state is gone; needs load()
            raise
        return scores

    def windows(self, dev: np.ndarray) -> tuple[jax.Array, jax.Array]:
        """Device-resident (x, valid) windows for `dev` — the query path
        (training snapshots use the host store instead)."""
        w = self.window
        d = jnp.asarray(dev.astype(np.int32))
        idx = (self.cursor[d][:, None] - w + jnp.arange(w)[None, :]) % w
        x = self.values[d[:, None], idx]
        valid = jnp.arange(w)[None, :] >= (w - self.count[d])[:, None]
        return x, valid

    def close(self) -> None:
        self._update_score_fns.clear()


class StackedDeviceRing:
    """Per-tenant device rings stacked on a leading tenant axis —
    the pooled (config 4) twin of `DeviceRing`.

    State leaves are `[T_cap, D_cap+1, window]` / `[T_cap, D_cap+1]`;
    with a mesh, the tenant axis is sharded over `model` (each device
    holds its tenants' rings resident, mirroring the stacked params in
    parallel/tenant_stack.py), so one vmapped XLA call appends + scores
    EVERY tenant with no host-side window materialization and no
    per-tenant dispatch. Padding writes land in each tenant's scratch
    row `D_cap`.
    """

    def __init__(self, window: int, n_tenants: int, device_cap: int = 1024,
                 mesh=None, score_dtype=None):
        from sitewhere_tpu.parallel.mesh import (
            megabatch_placer,
            tenant_placer,
        )

        self.window = int(window)
        self.mesh = mesh
        self.t_cap = int(n_tenants)
        self.device_cap = grow_pow2(int(device_cap), floor=1024)
        self.score_dtype = jnp.dtype(score_dtype) if score_dtype else None
        self._fns: dict[tuple, Callable] = {}
        self.faulted = False
        self._place = tenant_placer(mesh)
        # dispatch inputs ([T_cap, B] deltas) shard tenant-rows over
        # `model` and batch-columns over `data` — the serving-mesh axis
        # convention (parallel/mesh.py), XLA inserting the collectives
        self._place_in = megabatch_placer(mesh)
        self._alloc()

    def _alloc(self) -> None:
        t, d, w = self.t_cap, self.device_cap, self.window
        self.values = self._place(jnp.zeros((t, d + 1, w), jnp.float32))
        self.count = self._place(jnp.zeros((t, d + 1), jnp.int32))
        self.cursor = self._place(jnp.zeros((t, d + 1), jnp.int32))

    def ensure(self, n_tenants: int, max_device: int) -> None:
        """Grow either axis (device-side); recompiles lazily per shape.

        The tenant axis adopts `n_tenants` exactly — it must equal the
        param stack's capacity (vmap needs matching leading dims); the
        stack already grows geometrically, so this stays amortized."""
        new_t = max(self.t_cap, n_tenants)
        new_d = self.device_cap
        if max_device >= new_d:
            new_d = grow_pow2(max_device + 1, floor=new_d * 2)
        if new_t == self.t_cap and new_d == self.device_cap:
            return
        grow_t, grow_d = new_t - self.t_cap, new_d - self.device_cap
        self.values = self._place(jnp.pad(
            self.values[:, :-1], ((0, grow_t), (0, grow_d + 1), (0, 0))))
        self.count = self._place(jnp.pad(
            self.count[:, :-1], ((0, grow_t), (0, grow_d + 1))))
        self.cursor = self._place(jnp.pad(
            self.cursor[:, :-1], ((0, grow_t), (0, grow_d + 1))))
        self.t_cap, self.device_cap = new_t, new_d

    def load_tenant(self, slot: int, values: np.ndarray,
                    count: np.ndarray) -> None:
        """Seed one tenant's rings from host window data (chronological,
        left-padded — the `TelemetryStore.window` layout)."""
        n, w = values.shape
        assert w == self.window
        self.ensure(slot + 1, n - 1 if n else 0)
        cnt = np.minimum(count.astype(np.int32), w)
        idx = (np.arange(w)[None, :] + (w - cnt)[:, None]) % w
        ring_rows = np.take_along_axis(values.astype(np.float32), idx, axis=1)
        self.values = self._place(self.values.at[slot, :n].set(ring_rows))
        self.count = self._place(self.count.at[slot, :n].set(cnt))
        self.cursor = self._place(self.cursor.at[slot, :n].set(cnt % w))
        self.faulted = False

    def clear_tenant(self, slot: int) -> None:
        """Zero a departed tenant's rings (slot reuse must not leak)."""
        self.values = self._place(self.values.at[slot].set(0.0))
        self.count = self._place(self.count.at[slot].set(0))
        self.cursor = self._place(self.cursor.at[slot].set(0))

    def _build_score(self, model) -> Callable:
        w = self.window
        out_dtype = self.score_dtype

        def tenant_step(params, vals, cnt, cur, dev, v):
            return _append_window_score(w, model.score, out_dtype,
                                        params, vals, cnt, cur, dev, v)

        return jax.jit(jax.vmap(tenant_step), donate_argnums=(1, 2, 3))

    def padding(self, bucket: int) -> np.ndarray:
        """What a tenant row of `bucket` slots holds where it has no
        event: the scratch row."""
        return np.full(bucket, self.device_cap, np.int32)

    def _pad(self, dev: np.ndarray, v: np.ndarray) -> tuple:
        """dev/v are already [T_cap, B]; host fills padding with
        `padding` (the scratch row) before calling. Placement shards
        them over the mesh (tenant→model, batch→data) when one exists."""
        return (self._place_in(dev), self._place_in(v))

    def update_and_score(self, model, stacked_params, dev: np.ndarray,
                         v: np.ndarray) -> jax.Array:
        """dev: [T_cap, B] int32 (scratch-row-padded), v: [T_cap, B]
        float32 → [T_cap, B] scores on device (async)."""
        key = ("s", self.t_cap, self.device_cap, dev.shape[1])
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._build_score(model)
        try:
            self.values, self.count, self.cursor, scores = fn(
                stacked_params, self.values, self.count, self.cursor,
                *self._pad(dev, v))
        except Exception:
            self.faulted = True
            raise
        return scores

    def close(self) -> None:
        self._fns.clear()
