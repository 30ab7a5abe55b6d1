"""Ring attention: sequence/context parallelism over a device mesh.

SURVEY.md §5.7 / §2.4: the reference has no long-context machinery at
all [ABSENT]; here the "sequence" is a device's telemetry history, and
histories longer than one chip's HBM (or one kernel's appetite) shard
the TIME axis across mesh devices. Attention then needs every (q, k)
pair across shards: instead of all-gathering K/V (memory O(W) per
device), the K/V blocks ROTATE around the mesh ring via `ppermute`
while each device keeps only its query block — the ring-attention
pattern (Liu et al. 2023; blockwise online-softmax accumulation from
flash attention). Peak memory per device stays O(W/P), and the
per-step transfer rides ICI neighbor links, exactly what the mesh
topology is built for.

Layout contract (shard_map body, per device):
  q, k, v: [B, T_local, H, Dh]   — T_local = W / axis_size
  valid:   [B, T_local]          — False for padded slots
Accumulation is float32 regardless of input dtype; matmuls run in the
input dtype (bfloat16 on TPU → MXU).

`ring_attention` is the primitive (already inside shard_map /
pjit-traced code); `ring_attention_sharded` is the host-facing wrapper
that builds the shard_map over a mesh axis.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, kv_valid, scale, causal, q_pos, k_pos):
    """Scores of the local query block against ONE K/V block, returning
    the pieces online-softmax accumulation needs.

    q: [B, Tq, H, Dh]; k/v: [B, Tk, H, Dh]; kv_valid: [B, Tk]
    q_pos: [Tq] global positions; k_pos: [Tk] global positions.
    → scores [B, H, Tq, Tk] (masked, f32)
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = kv_valid[:, None, None, :]                      # [B, 1, 1, Tk]
    if causal:
        mask = jnp.logical_and(
            mask, (k_pos[None, None, None, :] <= q_pos[None, None, :, None]))
    return jnp.where(mask, scores, NEG_INF)


def ring_attention(q, k, v, valid, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   axis_size: Optional[int] = None):
    """Blockwise ring attention inside a shard_map over `axis_name`.

    Every device holds its local blocks; K/V (+validity) rotate P-1 hops
    around the ring while the online softmax folds each visiting block
    into the local queries' accumulator (the final fold does NOT rotate
    — the blocks are back where attention needs them, and a P-th
    rotation would be a wasted ICI round trip). Returns
    [B, T_local, H, Dh] (f32) — same layout as the inputs.
    """
    P_sz = int(axis_size) if axis_size is not None \
        else jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, T_l, H, Dh = q.shape
    scale = scale if scale is not None else Dh ** -0.5

    q_pos = idx * T_l + jnp.arange(T_l)

    def k_positions(block_owner):
        return block_owner * T_l + jnp.arange(T_l)

    # online-softmax state: accumulator o, running max m, running denom l
    # (pcast to varying: the carries become device-varying after the
    # first fold, so their init must be typed so for shard_map's scan)
    def varying(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    o = varying(jnp.zeros((B, T_l, H, Dh), jnp.float32))
    m = varying(jnp.full((B, H, T_l), NEG_INF, jnp.float32))
    l = varying(jnp.zeros((B, H, T_l), jnp.float32))

    perm = [(i, (i + 1) % P_sz) for i in range(P_sz)]

    def accumulate(o, m, l, k_cur, v_cur, valid_cur, step):
        owner = (idx - step) % P_sz          # whose block is visiting
        scores = _block_attend(q, k_cur, v_cur, valid_cur, scale, causal,
                               q_pos, k_positions(owner))
        blk_max = scores.max(-1)                              # [B, H, Tq]
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])                # [B,H,Tq,Tk]
        # a fully-masked row (all NEG_INF so far) must not contribute
        p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
        l = l * corr + p.sum(-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        o = o * corr.transpose(0, 2, 1)[..., None] + pv
        return o, new_m, l

    def fold(state, step):
        o, m, l, k_cur, v_cur, valid_cur = state
        o, m, l = accumulate(o, m, l, k_cur, v_cur, valid_cur, step)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        valid_nxt = jax.lax.ppermute(valid_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt, valid_nxt), None

    if P_sz > 1:  # P-1 rotating folds, then one final fold with no rotate
        (o, m, l, k, v, valid), _ = jax.lax.scan(
            fold, (o, m, l, k, v, valid), jnp.arange(P_sz - 1))
    o, m, l = accumulate(o, m, l, k, v, valid, P_sz - 1)
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o / denom


def ring_attention_sharded(q, k, v, valid, mesh: Mesh, seq_axis: str,
                           causal: bool = False):
    """Host-facing wrapper: shard the TIME axis of q/k/v/valid over mesh
    axis `seq_axis` and run ring attention. Shapes: q/k/v [B, W, H, Dh],
    valid [B, W]; W must divide by the axis size."""
    spec_qkv = P(None, seq_axis, None, None)
    spec_valid = P(None, seq_axis)

    axis_size = mesh.shape[seq_axis]

    def body(q, k, v, valid):
        return ring_attention(q, k, v, valid, seq_axis, causal=causal,
                              axis_size=axis_size)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_valid),
        out_specs=spec_qkv)
    args = (jax.device_put(q, NamedSharding(mesh, spec_qkv)),
            jax.device_put(k, NamedSharding(mesh, spec_qkv)),
            jax.device_put(v, NamedSharding(mesh, spec_qkv)),
            jax.device_put(valid, NamedSharding(mesh, spec_valid)))
    return fn(*args)


def dense_attention_reference(q, k, v, valid, causal: bool = False,
                              scale: Optional[float] = None):
    """O(W²)-memory reference (tests pin ring == dense)."""
    B, W, H, Dh = q.shape
    scale = scale if scale is not None else Dh ** -0.5
    pos = jnp.arange(W)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = valid[:, None, None, :]
    if causal:
        mask = jnp.logical_and(mask, pos[None, None, None, :]
                               <= pos[None, None, :, None])
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    # rows with no valid key at all: zero output (ring path matches)
    w = jnp.where(mask.any(-1, keepdims=True), w, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
