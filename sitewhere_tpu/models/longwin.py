"""Long-window blockwise transformer forecaster (sequence-parallel).

SURVEY.md §5.7's design slot made real: when a device's telemetry
history outgrows one chip's appetite (weekly seasonality at 1-minute
cadence is a 10k-step window), the TIME axis shards across a mesh axis
and attention runs as ring attention (parallel/ring.py) — peak memory
per device O(W/P), K/V blocks riding ICI neighbor links. The reference
platform has no analog [ABSENT]; this is the capability the north star's
"forecasting over long histories" needs.

Architecture: scalar embedding + sinusoidal positions → L pre-LN causal
transformer blocks (ring or dense attention; GLU feed-forward) → per-
position next-step quantile heads. Everything except attention is
per-timestep, so the whole stack lives inside one shard_map when a mesh
is given — embeddings, blocks, and heads all compute on time shards.

Scoring contract matches every registry model (`init`, `score`, `loss`
over `x[B, W]`, `valid[B, W]`): the anomaly score is the newest
observation's violation of the model's predicted quantile interval,
mirroring the TFT scorer, so the same rule-processing hook serves it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sitewhere_tpu.models.common import dense_init
from sitewhere_tpu.parallel.ring import (
    dense_attention_reference,
    ring_attention,
)


@dataclass(frozen=True)
class LongWindowConfig:
    window: int = 512
    hidden: int = 32
    heads: int = 4
    layers: int = 2
    quantiles: tuple[float, ...] = (0.1, 0.5, 0.9)
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0
    min_history: int = 32
    seq_axis: str = "data"      # mesh axis the time dimension shards over


def _ln(x):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6)


class LongWindowModel:
    """Functional long-window forecaster; optional mesh → sequence
    parallel. Instances hold config (and mesh) only — params are always
    passed explicitly."""

    name = "longwin"

    def __init__(self, cfg: LongWindowConfig = LongWindowConfig(),
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.mesh = mesh
        self._axis_size = None if mesh is None else mesh.shape[cfg.seq_axis]
        if mesh is not None:
            assert cfg.window % mesh.shape[cfg.seq_axis] == 0, \
                "window must divide across the sequence axis"

    # -- params ------------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        cfg = self.cfg
        d, h = cfg.hidden, cfg.heads
        ks = iter(jax.random.split(rng, 3 + 6 * cfg.layers))
        params: dict = {
            "embed": dense_init(next(ks), 2, d),   # (value, is-valid) → d
            "head": dense_init(next(ks), d, len(cfg.quantiles)),
        }
        for i in range(cfg.layers):
            params[f"block{i}"] = {
                "q": dense_init(next(ks), d, d),
                "k": dense_init(next(ks), d, d),
                "v": dense_init(next(ks), d, d),
                "o": dense_init(next(ks), d, d),
                "ff_in": dense_init(next(ks), d, 4 * d),
                "ff_out": dense_init(next(ks), 2 * d, d),
            }
        return params

    # -- forward -----------------------------------------------------------

    def _normalize(self, x, valid):
        n = jnp.maximum(valid.sum(-1, keepdims=True), 1.0)
        mu = (x * valid).sum(-1, keepdims=True) / n
        var = (((x - mu) * valid) ** 2).sum(-1, keepdims=True) / n
        sd = jnp.sqrt(var + 1e-6)
        return (x - mu) / sd, mu, sd

    def _positions(self, t_local: int, axis_name: Optional[str]):
        if axis_name is None:
            return jnp.arange(t_local)
        return jax.lax.axis_index(axis_name) * t_local + jnp.arange(t_local)

    def _stack(self, params, xn, valid, axis_name: Optional[str]):
        """Per-timestep stack; runs on a time shard when axis_name set.
        xn: [B, T] normalized values → quantile deltas [B, T, Q]."""
        cfg = self.cfg
        cdt = cfg.compute_dtype
        d, H = cfg.hidden, cfg.heads
        Dh = d // H
        B, T = xn.shape
        pos = self._positions(T, axis_name)
        # sinusoidal positional features added to the scalar embedding
        freqs = jnp.exp(-jnp.arange(d // 2) * (8.0 / max(d // 2 - 1, 1)))
        ang = pos[:, None] * freqs[None, :]
        posenc = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)  # [T, d]
        feats = jnp.stack([xn, valid.astype(jnp.float32)], -1)      # [B,T,2]
        hx = (feats.astype(cdt) @ params["embed"]["w"].astype(cdt)
              ).astype(jnp.float32) + params["embed"]["b"] + posenc[None]
        for i in range(cfg.layers):
            p = params[f"block{i}"]
            hn = _ln(hx).astype(cdt)
            q = (hn @ p["q"]["w"].astype(cdt)).reshape(B, T, H, Dh)
            k = (hn @ p["k"]["w"].astype(cdt)).reshape(B, T, H, Dh)
            v = (hn @ p["v"]["w"].astype(cdt)).reshape(B, T, H, Dh)
            if axis_name is None:
                attn = dense_attention_reference(q, k, v, valid, causal=True)
            else:
                attn = ring_attention(q, k, v, valid, axis_name, causal=True,
                                      axis_size=self._axis_size)
            attn = attn.reshape(B, T, d)
            hx = hx + (attn.astype(cdt) @ p["o"]["w"].astype(cdt)
                       ).astype(jnp.float32) + p["o"]["b"]
            hn = _ln(hx).astype(cdt)
            ff = (hn @ p["ff_in"]["w"].astype(cdt)).astype(jnp.float32) \
                + p["ff_in"]["b"]
            a, g = jnp.split(ff, 2, axis=-1)
            ff = (a * jax.nn.sigmoid(g)).astype(cdt)
            hx = hx + (ff @ p["ff_out"]["w"].astype(cdt)
                       ).astype(jnp.float32) + p["ff_out"]["b"]
        head = params["head"]
        dq = (_ln(hx).astype(cdt) @ head["w"].astype(cdt)
              ).astype(jnp.float32) + head["b"]
        return dq                                             # [B, T, Q]

    def _quantile_deltas(self, params, xn, valid):
        """Quantile predictions for the NEXT step at every position.
        Runs sequence-parallel when a mesh is configured."""
        if self.mesh is None:
            return self._stack(params, xn, valid, None)
        ax = self.cfg.seq_axis
        spec_x = P(None, ax)

        def body(xn, valid):
            return self._stack(params, xn, valid, ax)

        return jax.shard_map(
            body, mesh=self.mesh, in_specs=(spec_x, spec_x),
            out_specs=P(None, ax, None))(xn, valid)

    # -- registry contract -------------------------------------------------

    def score(self, params: dict, x: jax.Array, valid: jax.Array) -> jax.Array:
        """Anomaly score: the newest observation's violation of the
        quantile interval predicted at the previous step. [B, W] → [B]."""
        cfg = self.cfg
        v = valid.astype(jnp.float32)
        xn, _, sd = self._normalize(x, v)
        dq = self._quantile_deltas(params, xn, v)             # [B, W, Q]
        lo, mid, hi = dq[:, -2, 0], dq[:, -2, len(cfg.quantiles) // 2], \
            dq[:, -2, -1]
        newest = xn[:, -1]
        width = jnp.maximum(hi - lo, 1e-3)
        over = jnp.maximum(newest - hi, 0.0) / width
        under = jnp.maximum(lo - newest, 0.0) / width
        err = jnp.abs(newest - mid) / width
        score = over + under + 0.1 * err
        enough = v.sum(-1) >= cfg.min_history
        return jnp.clip(jnp.where(enough, score, 0.0), 0.0, cfg.score_clip)

    def flops_per_event(self) -> float:
        """Approximate forward FLOPs per scored window: per layer, the
        MLP/projection matmuls (~8 d*d per step) plus blockwise attention
        (4*W*d per step). Coarse estimate for MFU accounting."""
        cfg = self.cfg
        d, w = cfg.hidden, cfg.window
        per_layer = w * (8.0 * d * d + 4.0 * w * d)
        return cfg.layers * per_layer

    def loss(self, params: dict, x: jax.Array, valid: jax.Array) -> jax.Array:
        """Pinball (quantile) loss of each position's next-step
        prediction against the realized value, masked to valid pairs."""
        cfg = self.cfg
        v = valid.astype(jnp.float32)
        xn, _, _ = self._normalize(x, v)
        dq = self._quantile_deltas(params, xn, v)             # [B, W, Q]
        pred = dq[:, :-1]                                     # predicts t+1
        target = xn[:, 1:, None]
        qs = jnp.asarray(cfg.quantiles)[None, None, :]
        diff = target - pred
        pin = jnp.maximum(qs * diff, (qs - 1.0) * diff)
        mask = (v[:, 1:] * v[:, :-1])[..., None]
        return (pin * mask).sum() / jnp.maximum(mask.sum(), 1.0)
