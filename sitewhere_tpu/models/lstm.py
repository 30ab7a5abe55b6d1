"""LSTM anomaly detector (config 2 [BASELINE.json]).

Self-supervised next-step forecaster over a device's recent telemetry
window; the anomaly score is the normalized one-step-ahead prediction
error at the newest point. Replaces the reference's CPU Siddhi/Groovy
rule evaluation at the same hook point [SURVEY.md §1 L5, §3.2].

TPU-first details:
- pure functional: params are a pytree; `score`/`loss` are jit/vmap/pjit
  friendly (static shapes, `lax.scan` over time, no Python branching).
- matmuls in bfloat16 (MXU), state/accumulation in float32.
- per-window normalization makes one set of weights serve heterogeneous
  fleets (different baselines/scales per device).
- the same `score` vmaps over a stacked leading tenant axis for
  per-tenant multiplexing without recompiles (config 4; SURVEY.md §7
  hard part b).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.models.common import dense_init, lstm_init, lstm_scan


@dataclass(frozen=True)
class LstmConfig:
    window: int = 64          # input history length W
    hidden: int = 64
    layers: int = 1
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0  # scores are z-like; clip insanity


class LstmAnomalyModel:
    """Functional LSTM forecaster. Instances hold config only — params
    are always passed explicitly (pjit/vmap need that)."""

    name = "lstm"

    def __init__(self, cfg: LstmConfig = LstmConfig()):
        self.cfg = cfg

    # -- params ------------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        cfg = self.cfg
        params = {}
        keys = jax.random.split(rng, cfg.layers + 1)
        in_dim = 1
        for layer in range(cfg.layers):
            params[f"lstm{layer}"] = lstm_init(keys[layer], in_dim, cfg.hidden)
            in_dim = cfg.hidden
        params["head"] = dense_init(keys[-1], cfg.hidden, 1)
        return params

    # -- forward -----------------------------------------------------------

    def _normalize(self, x: jax.Array, valid: jax.Array):
        """Per-window masked mean/std (padding slots excluded)."""
        n = jnp.maximum(valid.sum(-1, keepdims=True), 1.0)
        mu = (x * valid).sum(-1, keepdims=True) / n
        var = (((x - mu) * valid) ** 2).sum(-1, keepdims=True) / n
        sd = jnp.sqrt(var + 1e-6)
        return (x - mu) / sd, mu, sd

    def _predictions(self, params: dict, xn: jax.Array) -> jax.Array:
        """One-step-ahead predictions for steps 1..W-1.  xn: [B, W] → [B, W-1]."""
        cfg = self.cfg
        cdt = cfg.compute_dtype
        seq = xn[:, :-1, None].astype(cdt)                # [B, W-1, 1]
        for layer in range(cfg.layers):
            seq, _ = lstm_scan(params[f"lstm{layer}"], seq, cdt)
            seq = seq.astype(cdt)
        head = params["head"]
        preds = (seq.astype(jnp.float32) @ head["w"] + head["b"])[..., 0]
        return preds                                       # [B, W-1]

    def _finalize(self, pred_last: jax.Array, xn: jax.Array,
                  valid: jax.Array) -> jax.Array:
        """Shared scoring tail: |forecast error| at the newest step,
        short-history gate, clip — one implementation so `score` and
        `score_fused` cannot drift."""
        err = jnp.abs(pred_last - xn[:, -1])
        # rows with too little history can't be judged → score 0
        enough = valid.sum(-1) >= max(8, self.cfg.window // 8)
        return jnp.clip(jnp.where(enough, err, 0.0), 0.0, self.cfg.score_clip)

    def score(self, params: dict, x: jax.Array, valid: jax.Array) -> jax.Array:
        """Anomaly score per row: normalized |forecast error| at the newest
        step. x: [B, W] raw values; valid: [B, W] bool. → [B] float32."""
        xn, _, _ = self._normalize(x, valid.astype(jnp.float32))
        preds = self._predictions(params, xn)
        return self._finalize(preds[:, -1], xn, valid)

    def forecast(self, params: dict, x: jax.Array,
                 valid: jax.Array) -> jax.Array:
        """One-step-ahead point forecast in ORIGINAL units: [B, 1, 1]
        (the uniform [B, H, Q] forecast shape; the TFT's multi-horizon
        quantile twin is models/tft.py `forecast`).

        Runs the cell over ALL W observed steps and takes the output
        after the last one — the prediction of the NEXT, unseen value
        (`_predictions` feeds xn[:, :-1] because scoring compares
        pred(t) with the observed x_t; a forecast must not stop one
        step short or it merely reconstructs the newest observation)."""
        cfg = self.cfg
        xn, mu, sd = self._normalize(x, valid.astype(jnp.float32))
        seq = xn[:, :, None].astype(cfg.compute_dtype)
        for layer in range(cfg.layers):
            seq, _ = lstm_scan(params[f"lstm{layer}"], seq,
                               cfg.compute_dtype)
            seq = seq.astype(cfg.compute_dtype)
        head = params["head"]
        pred_n = (seq[:, -1].astype(jnp.float32) @ head["w"]
                  + head["b"])[:, 0]
        pred = pred_n * sd[:, 0] + mu[:, 0]
        return pred[:, None, None]

    def score_fused(self, params: dict, x: jax.Array,
                    valid: jax.Array) -> jax.Array:
        """`score` with the recurrence in the Pallas fused-window kernel
        when eligible (single layer, tile-divisible batch, real TPU —
        ops/lstm_kernel.py); identical semantics, reference fallback
        otherwise. Scoring needs only the LAST step's prediction, so the
        kernel keeps h/c + weights in VMEM across all W-1 steps and
        writes back one [B, h] tensor. Used by the dedicated windowed
        ring's flush jit (never under vmap — the stacked/pooled path
        keeps `score`, whose lax.scan batches under vmap)."""
        from sitewhere_tpu.ops.lstm_kernel import lstm_window_final, pallas_ok

        cfg = self.cfg
        if not pallas_ok(int(x.shape[0]), cfg.layers, cfg.compute_dtype):
            return self.score(params, x, valid)
        xn, _, _ = self._normalize(x, valid.astype(jnp.float32))
        h = lstm_window_final(params["lstm0"], xn[:, :-1], cfg.compute_dtype)
        head = params["head"]
        pred = (h @ head["w"] + head["b"])[:, 0]
        return self._finalize(pred, xn, valid)

    def flops_per_event(self) -> float:
        """Approximate forward FLOPs to score ONE event (one window row):
        4 LSTM gates × 2 FLOPs/MAC per scan step, plus the head. Used for
        the bench's MFU accounting (model FLOP/s vs chip peak)."""
        cfg = self.cfg
        h, steps = cfg.hidden, cfg.window - 1
        fl, in_dim = 0.0, 1
        for _ in range(cfg.layers):
            fl += steps * 8.0 * h * (in_dim + h)
            in_dim = h
        return fl + steps * 2.0 * h  # head projection

    def loss(self, params: dict, x: jax.Array, valid: jax.Array) -> jax.Array:
        """Masked next-step MSE over the window (self-supervised)."""
        v = valid.astype(jnp.float32)
        xn, _, _ = self._normalize(x, v)
        preds = self._predictions(params, xn)
        target = xn[:, 1:]
        mask = v[:, 1:] * v[:, :-1]
        se = (preds - target) ** 2 * mask
        return se.sum() / jnp.maximum(mask.sum(), 1.0)


# the per-row scalars of `StreamingLstmModel`'s row leaf, in lane order
_ROW_SCALARS = ("pred", "mean", "var", "count")


class StreamingLstmModel(LstmAnomalyModel):
    """Event-native streaming twin of the windowed LSTM scorer.

    The windowed model re-scans the whole W-step history for EVERY new
    event — W-1 sequential cell steps (≈2.1 MFLOPs/event at W=64 h=64)
    to produce one score, which measured out at ~45 ms per 16k-event
    flush on a v5e chip: the scan, not the host, was the throughput
    ceiling. Streaming is the TPU-native fix: per-device LSTM state
    (h, c per layer), the standing next-step prediction, and running
    normalization stats live in HBM (scoring/stream.py), and each event
    costs ONE cell step (≈33 KFLOPs at h=64) — a ~63× compute cut on
    the same weights.

    Scoring semantics: score(t) = |prediction made at t-1 − x_t| in
    normalized space, gated on history count like the windowed model.
    Normalization uses per-device capped-count Welford stats (count
    capped at W), the streaming analog of the window mean/std — so
    params TRAINED on the windowed objective (`loss` above) serve
    directly; the two scorers agree to within normalization drift.

    `score`/`loss` (whole-window paths: query/REST, training) are
    inherited unchanged — only the resident hot path differs.

    State: ONE leaf `row`, f32 `[rows, k, 128]` with `k * 128 =
    round_up(2 * hidden * layers + 4, 128)` values a row: `h0 ‖ c0 ‖ h1 ‖
    c1 ...`, then the four per-row scalars `pred`, `mean`, `var`, `count`
    (`scalars` names them; `count` is a whole number in a float32 lane,
    capped at the window and exact far past it), then zero padding that
    is never read. One leaf is one row gather and one row scatter a step,
    where each scalar leaf of its own cost a gather and a scatter over
    the whole fleet. `[rows, k, 128]` and not `[rows, k * 128]` on a
    reading alone: the same 1 KB a row at rest at the served widths
    (of which 528 B are used), and a v5e's compiler scatters 16,384 rows
    into the first in 1.03 ms, into the second in 1.45 (PERF.md section
    6, PR 31).
    """

    name = "lstm-stream"
    streaming = True

    def _hc_width(self) -> int:
        """Values of `h` and `c` of all layers: where the scalars start."""
        return 2 * self.cfg.hidden * self.cfg.layers

    def _row_tiles(self) -> int:
        return -(-(self._hc_width() + len(_ROW_SCALARS)) // 128)

    def _row_join(self, parts: list, pred, mean, var, count) -> jax.Array:
        """`[h0, c0, h1, c1, ...]`, each `[B, hidden]`, and the four
        scalars `[B]` → `[B, k, 128]`."""
        parts = parts + [jnp.stack([pred, mean, var, count], axis=-1)]
        pad = (self._row_tiles() * 128 - self._hc_width()
               - len(_ROW_SCALARS))
        if pad:
            parts.append(jnp.zeros((parts[0].shape[0], pad), jnp.float32))
        return jnp.concatenate(parts, axis=-1).reshape(
            -1, self._row_tiles(), 128)

    def scalars(self, row: jax.Array) -> dict:
        """The per-row scalars of `row` `[..., k, 128]` by name."""
        flat = row.reshape(row.shape[:-2] + (-1,))
        at = self._hc_width()
        return {name: flat[..., at + i]
                for i, name in enumerate(_ROW_SCALARS)}

    def init_state(self, cap: int) -> dict:
        """Zero per-device streaming state for `cap` rows (callers add
        their own scratch row before passing a capacity here)."""
        tiles = self._row_tiles()
        row = jnp.zeros((cap, tiles * 128), jnp.float32).at[
            :, self._hc_width() + _ROW_SCALARS.index("var")].set(1.0)
        return {"row": row.reshape(cap, tiles, 128)}

    def _cell(self, params: dict, layer: int, x: jax.Array,
              h: jax.Array, c: jax.Array):
        """One fused-gate LSTM step. x: [B, d_in] → (h, c) [B, hidden]."""
        cdt = self.cfg.compute_dtype
        p = params[f"lstm{layer}"]
        gates = (x.astype(cdt) @ p["wx"].astype(cdt)).astype(jnp.float32) \
            + (h.astype(cdt) @ p["wh"].astype(cdt)).astype(jnp.float32) \
            + p["b"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return h, c

    def step_score(self, params: dict, rows: dict, v: jax.Array):
        """Score + advance gathered state rows for one event each.

        rows: the state leaf indexed down to the event batch
        ([B, k, 128]); v: [B] raw values. Returns (scores [B], new rows)."""
        cfg = self.cfg
        hid = cfg.hidden
        pred, mean, var, cnt = self.scalars(rows["row"]).values()
        row = rows["row"].reshape(v.shape[0], -1)
        sd = jnp.sqrt(var + 1e-6)
        xn = (v - mean) / sd
        enough = cnt >= max(8, cfg.window // 8)
        score = jnp.clip(jnp.where(enough, jnp.abs(xn - pred), 0.0),
                         0.0, cfg.score_clip)
        # capped-count Welford: behaves like the window-W mean/std once
        # count saturates (the streaming analog of _normalize)
        cnt1 = jnp.minimum(cnt + 1, cfg.window)
        delta = v - mean
        mean1 = mean + delta / cnt1
        var1 = var + ((v - mean1) * delta - var) / cnt1
        x = ((v - mean1) / jnp.sqrt(var1 + 1e-6))[:, None]
        parts = []
        for layer in range(cfg.layers):
            at = 2 * layer * hid
            h, c = self._cell(params, layer, x, row[:, at:at + hid],
                              row[:, at + hid:at + 2 * hid])
            parts += [h, c]
            x = h
        head = params["head"]
        pred1 = (x @ head["w"] + head["b"])[:, 0]
        return score, {"row": self._row_join(parts, pred1, mean1, var1,
                                             cnt1)}

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """Build streaming state for `n` devices by replaying their host
        windows (x: [n, W] chronological left-padded, valid: [n, W]) —
        the warmup/recovery seed, one scan call for the whole fleet."""
        from sitewhere_tpu.models.common import lstm_scan

        cfg = self.cfg
        v = valid.astype(jnp.float32)
        n = jnp.maximum(v.sum(-1), 1.0)
        mean = (x * v).sum(-1) / n
        var = (((x - mean[:, None]) * v) ** 2).sum(-1) / n
        xn = ((x - mean[:, None]) / jnp.sqrt(var + 1e-6)[:, None]) * v
        seq, parts = xn[:, :, None], []
        for layer in range(cfg.layers):
            seq, (h, c) = lstm_scan(params[f"lstm{layer}"], seq,
                                    cfg.compute_dtype)
            seq = seq.astype(cfg.compute_dtype)
            parts += [h, c]
        head = params["head"]
        pred = (seq[:, -1, :].astype(jnp.float32) @ head["w"] + head["b"])[:, 0]
        return {"row": self._row_join(
            parts, pred, mean, jnp.maximum(var, 1e-6),
            jnp.minimum(v.sum(-1), float(cfg.window)))}

    def flops_per_event(self) -> float:
        """One cell step per event (vs a W-1-step rescan)."""
        cfg = self.cfg
        h = cfg.hidden
        fl, in_dim = 0.0, 1
        for _ in range(cfg.layers):
            fl += 8.0 * h * (in_dim + h)
            in_dim = h
        return fl + 2.0 * h
