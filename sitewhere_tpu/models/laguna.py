"""`laguna-stream`: a Laguna-S-2.1 block stack as a streaming anomaly
scorer (tokens, score and gate as models/seqblocks.py has them).

The block is the published one (config.json of poolside/Laguna-S-2.1;
the configuration's keys keep their published names, so a catalog row
can be handed over as it is). Layer `l` has `n_l =
num_attention_heads_per_layer[l]` query heads over
`num_key_value_heads` key-value heads of `head_dim`, and is of one of
two kinds (`layer_types[l]`):

    u = RMSNorm(x);  q = u Wq [n_l, d];  k = u Wk, v = u Wv [kv, d]
    q, k <- rope_l(., t);  the layer's context gains (k, v) at t
    a_h = softmax(q_h K_{h // g}^T / sqrt(d)) V_{h // g},  g = n_l / kv
    o = concat_h(sigmoid(u Wg)_h * a_h) Wo;  x <- x + o

`full_attention`: every position `j <= t`; rope by the tables of
`rope_parameters.full_attention` (YaRN on the first
`partial_rotary_factor` of each head's dimensions, cos and sin times
`attention_factor`). `sliding_attention`: positions `t -
sliding_window < j <= t`; plain rope on every dimension. Then `u2 =
RMSNorm(x)` and a dense SiLU-gated MLP (`mlp_layer_types[l] ==
"dense"`) or the expert layer: `p = softmax(u2 Wr)` over `num_experts`,
the `num_experts_per_tok` largest kept, `w = moe_routed_scaling_factor
* p_kept / sum(p_kept)`, `x <- x + sum_{e kept and held} w_e MLP_e(u2)
+ MLP_shared(u2)`.

What the config leaves open is set by the convention of its key names
(the benchmark's configuration lists each under `assumed`): the gate is
the head-wise form of gated attention (`gating: per-head`), a sigmoid
of a linear map of the normed layer input, one scalar a head, on the
attention output before `Wo`; the router is a softmax with no
selection bias; the shared expert is added ungated; the MLPs are
SiLU-gated; no query or key norm; rope turns pairs `(2i, 2i + 1)`.

The share held here: `(first_expert, num_experts_held)` of each expert
layer and `vocab_held` rows of the embedding and the head
(models/seqblocks.py, `Experts`).

Weights in `compute_dtype`, products in it with float32 accumulation;
router, softmaxes, norms, gate, residual stream and score in float32.

State leaves (scoring/stream.py, "Contract with the model"): `mean`,
`var` f32, `count`, `pos` i32 `[rows]`; `hn` `[rows, hidden]`; and TWO
window leaves a layer, `k<l>` and `v<l>` `[rows, positions, kv * d]`
(whole lane tiles: 1,024 values a position at the published widths). A
full layer's hold `context_positions` and are the bounded ones: they
say when a row is full. A sliding layer's hold `sliding_window`
positions and WRAP (`wraps`): position `t` rests at `t mod
sliding_window`, over the position that has just left the window.
Rope is applied before an entry is stored, so the order of the slots
means nothing to the softmax. Two forms of the same numbers: the
prefill form over a stored window (seeding, the query path; banded
causal mask on sliding layers) and the decode form for the ring step,
which reads a context as it rests: the query is laid out block-
diagonally over the `kv * d` lanes of a position, so one product a
layer takes every head's logits from the leaf without reshaping it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.models import seqblocks
from sitewhere_tpu.models.seqblocks import (
    SEED_TOKENS,
    Experts,
    SeqBlocks,
    rms,
    rope,
    runs_one_tile,
)

_LAYERS = 48              # the published depth: periods of four


def _rope_parameters() -> dict:
    return {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1}}


def _periods(first, rest) -> list:
    return ([first] + [rest] * 3) * (_LAYERS // 4)


@dataclass(frozen=True)
class LagunaConfig:
    # the published config.json's keys, defaults as published
    model_type: str = "laguna"
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = _LAYERS
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: list = field(default_factory=lambda: [0])
    tie_word_embeddings: bool = False
    gating: str = "per-head"
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=_rope_parameters)
    layer_types: list = field(default_factory=lambda: _periods(
        "full_attention", "sliding_attention"))
    moe_apply_router_weight_on_input: bool = False
    mlp_layer_types: list = field(
        default_factory=lambda: ["dense"] + ["sparse"] * (_LAYERS - 1))
    gating_types: list = field(
        default_factory=lambda: ["per_head"] * _LAYERS)
    moe_routed_scaling_factor: float = 2.5
    num_attention_heads_per_layer: list = field(
        default_factory=lambda: _periods(48, 72))
    moe_router_logit_softcapping: float = 0
    # the share of a layer this chip holds (0: all of it)
    first_expert: int = 0
    num_experts_held: int = 0
    vocab_held: int = 0
    # the streaming scorer round the model
    window: int = 528             # stored values a context is seeded from
    context_positions: int = 768  # positions a full layer's context holds
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0

    @property
    def experts_held(self) -> int:
        return self.num_experts_held or self.num_experts

    @property
    def vocab(self) -> int:
        return self.vocab_held or self.vocab_size

    @property
    def kv_width(self) -> int:
        """A position's keys (or values) as they are stored."""
        return self.num_key_value_heads * self.head_dim


class LagunaStreamModel(SeqBlocks):
    """Functional, like every model here: the instance holds the
    configuration and tables made from it, weights are passed in."""

    name = "laguna-stream"
    streaming = True
    # the numbers `step_score` returns beside the scores, by the names
    # the session feeds the metrics registry under (`scoring.<name>`)
    step_stats = ("moe.assignments_held", "moe.assignments",
                  "moe.expert_max_tokens", "ctx.positions",
                  "moe.runs_one_tile", "ctx.window_positions",
                  "ctx.wrapped", "ctx.at_rest", "ctx.read_positions")
    stat_families = (SeqBlocks.expert_stats, SeqBlocks.context_stats)

    def __init__(self, cfg: LagunaConfig = LagunaConfig()):
        n = cfg.num_hidden_layers
        for key, want in (("gating", "per-head"), ("norm_topk_prob", True),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("decoder_sparse_step", 1),
                          ("moe_apply_router_weight_on_input", False),
                          ("moe_router_logit_softcapping", 0)):
            if getattr(cfg, key) != want:
                raise ValueError(f"laguna-stream computes {key}={want!r} "
                                 f"only, not {getattr(cfg, key)!r}")
        for key in ("layer_types", "mlp_layer_types", "gating_types",
                    "num_attention_heads_per_layer"):
            if len(getattr(cfg, key)) < n:
                raise ValueError(f"{key} names fewer than {n} layers")
        self.kinds = list(cfg.layer_types[:n])
        self.heads = list(cfg.num_attention_heads_per_layer[:n])
        self.dense = [kind == "dense" for kind in cfg.mlp_layer_types[:n]]
        if set(self.kinds) - {"full_attention", "sliding_attention"} \
                or set(cfg.gating_types[:n]) != {"per_head"} \
                or any(h % cfg.num_key_value_heads for h in self.heads) \
                or [l for l in range(n) if self.dense[l]] != [
                    l for l in cfg.mlp_only_layers if l < n]:
            raise ValueError("laguna-stream: layer lists it cannot compute")
        if cfg.first_expert + cfg.experts_held > cfg.num_experts:
            raise ValueError("held experts reach past num_experts")
        if not cfg.window <= cfg.context_positions:
            raise ValueError("a context holds fewer positions than the "
                             "window it is seeded from")
        if cfg.kv_width % 128:
            raise ValueError("a position's keys are no whole lane tiles")
        self.cfg = cfg
        self.experts = Experts(
            routed=cfg.num_experts, held=cfg.experts_held,
            first=cfg.first_expert, per_token=cfg.num_experts_per_tok,
            scale=cfg.moe_routed_scaling_factor, scoring="softmax")
        self.layers = n
        # state leaves that are windows -> the leaf that holds the
        # position a step appends at; those of the sliding layers wrap
        # (scoring/stream.py)
        self.windows = {f"{kv}{l}": "pos" for l in range(n) for kv in "kv"}
        self.wraps = frozenset(
            f"{kv}{l}" for l in range(n) for kv in "kv"
            if self.kinds[l] == "sliding_attention")
        # ...and every one is read where it rests, in its layer's turn
        self.at_rest = tuple(self.windows)
        # rows one seeding call takes (StreamingRing.load blocks by it)
        self.seed_rows = max(1, SEED_TOKENS // cfg.window)
        self._gate = max(8, cfg.window // 8)
        self._scale = cfg.head_dim ** -0.5
        # a kind's rotated width and its tables over every position a
        # context can reach, the attention factor in them
        self._ropes = {}
        for kind in set(self.kinds):
            rp = cfg.rope_parameters[kind]
            dim = int(cfg.head_dim * rp.get("partial_rotary_factor", 1))
            cos, sin = seqblocks.rope_tables(
                cfg.context_positions, dim, rp["rope_theta"],
                rp if rp.get("rope_type") == "yarn" else None)
            factor = np.float32(rp.get("attention_factor") or 1.0)
            self._ropes[kind] = (dim, cos * factor, sin * factor)
        # one trace and one lowering for all of a program's expert
        # layers, whose shapes are the same (models/dsv3.py)
        self._routed = jax.jit(self.routed)

    def _positions(self, layer: int) -> int:
        c = self.cfg
        return (c.sliding_window if self._sliding(layer)
                else c.context_positions)

    # -- weights ------------------------------------------------------------

    def _block_shapes(self, layer: int) -> dict:
        c = self.cfg
        h, n, d = c.hidden_size, self.heads[layer], c.head_dim
        w, f = c.compute_dtype, jnp.float32

        def mlp(width):
            return {"gate": ((h, width), w), "up": ((h, width), w),
                    "down": ((width, h), w)}

        block = {"attn_norm": ((h,), f), "mlp_norm": ((h,), f),
                 "q": ((h, n * d), w), "k": ((h, c.kv_width), w),
                 "v": ((h, c.kv_width), w), "head_gate": ((h, n), w),
                 "o": ((n * d, h), w)}
        if self.dense[layer]:
            block["mlp"] = mlp(c.intermediate_size)
        else:
            block["router"] = {"w": ((c.num_experts, h), f)}
            block["shared"] = mlp(c.shared_expert_intermediate_size)
            # a leaf an expert: the step reads each where it rests
            block["experts"] = {f"e{e}": mlp(c.moe_intermediate_size)
                                for e in range(c.experts_held)}
        return block

    def param_shapes(self) -> dict:
        """The checkpoint's layout: name -> (shape, dtype), nested."""
        c = self.cfg
        h, w = c.hidden_size, c.compute_dtype
        shapes = {"embed": ((c.vocab, h), w), "norm": ((h,), jnp.float32),
                  "head": ((h, c.vocab), w)}
        for l in range(self.layers):
            shapes[f"layer{l}"] = self._block_shapes(l)
        return shapes

    # -- attention ------------------------------------------------------------

    def _project(self, layer, p, u, at):
        """Queries `[..., n_l, d]` and keys `[..., kv, d]` of normed
        tokens `u` `[..., hidden]` at positions `at` `[...]`, both
        turned by the layer's rope, and values `[..., kv * d]`: float32."""
        c = self.cfg
        d = c.head_dim
        q = self._mm(u, p["q"]).reshape(u.shape[:-1] + (self.heads[layer], d))
        k = self._mm(u, p["k"]).reshape(
            u.shape[:-1] + (c.num_key_value_heads, d))
        dim, cos, sin = self._ropes[self.kinds[layer]]
        cos = jnp.asarray(cos)[at][..., None, :]
        sin = jnp.asarray(sin)[at][..., None, :]

        def turn(x):
            if dim == d:
                return rope(x, cos, sin)
            return jnp.concatenate(
                [rope(x[..., :dim], cos, sin), x[..., dim:]], -1)

        return turn(q), turn(k), self._mm(u, p["v"])

    def _stored(self, k, v):
        """A position's context entries as they rest: `[..., kv * d]`
        keys and values in the compute type."""
        cdt = self.cfg.compute_dtype
        return k.reshape(k.shape[:-2] + (-1,)).astype(cdt), v.astype(cdt)

    def _sliding(self, layer: int) -> bool:
        return self.kinds[layer] == "sliding_attention"

    def _attend_prefill(self, layer, q, k, v, count):
        """The prefill form over `[n, S]` tokens, banded on a sliding
        layer (models/seqblocks.py). -> `[n, S, n_l, d]`."""
        c = self.cfg
        return self._causal_prefill(
            q, k, v, count, c.num_key_value_heads,
            c.sliding_window if self._sliding(layer) else None)

    def _attend_decode(self, layer, q, k, v, kctx, vctx, pos):
        """The decode form for one token a row over the layer's two
        window leaves where they rest; a sliding layer's context wraps
        (models/seqblocks.py). -> `[B, n_l, d]`."""
        return self._decode_at_rest(q, k, v, kctx, vctx, pos,
                                    self.cfg.num_key_value_heads,
                                    self._sliding(layer))

    def _attention(self, layer, p, x, at, attend):
        """The block's first half on the residual stream `x` `[...,
        hidden]` at positions `at`; `attend(q, k, v)` is the form. ->
        (x, the stored keys, the stored values)."""
        c = self.cfg
        u = rms(x, p["attn_norm"], c.rms_norm_eps)
        with jax.named_scope("gqa_project"):
            q, k, v = self._project(layer, p, u, at)
            k, v = self._stored(k, v)
        with jax.named_scope(
                "attn_window" if self._sliding(layer) else "attn_full"):
            a = attend(q, k, v)
        with jax.named_scope("head_gate"):
            a = self._gated(p, u, a)
            return x + self._mm(a.reshape(a.shape[:-2] + (-1,)), p["o"]), k, v

    def _gated(self, p, u, a):
        """Heads `a` `[..., n_l, d]` each times its gate, a sigmoid of a
        linear map of the normed layer input `u`."""
        return a * jax.nn.sigmoid(self._mm(u, p["head_gate"]))[..., None]

    def _block_prefill(self, layer, p, x, count):
        """One block over `[n, S, hidden]`; also the layer's context
        entries, keys and values `[n, S, kv * d]`."""
        n, s, hid = x.shape
        x, k, v = self._attention(
            layer, p, x, jnp.arange(s),
            lambda q, k, v: self._attend_prefill(layer, q, k, v, count))
        flat = rms(x, p["mlp_norm"], self.cfg.rms_norm_eps).reshape(
            n * s, hid)
        y, _ = self._ffn(p, flat, jnp.ones(n * s, bool))
        return x + y.reshape(n, s, hid), k, v

    def _block_decode(self, layer, p, x, kctx, vctx, pos, live):
        c = self.cfg
        x, _, _ = self._attention(
            layer, p, x, jnp.minimum(pos, c.context_positions - 1),
            lambda q, k, v: self._attend_decode(layer, q, k, v, kctx, vctx,
                                                pos))
        y, counts = self._ffn(p, rms(x, p["mlp_norm"], c.rms_norm_eps), live)
        return x + y, counts

    def _prefill(self, params, tokens, count):
        """Every block over `[n, S]` tokens: (hidden states before the
        final norm `[n, S, hidden]`, (keys, values) a layer)."""
        x = params["embed"][tokens].astype(jnp.float32)
        entries = []
        for l in range(self.layers):
            x, k, v = self._block_prefill(l, params[f"layer{l}"], x, count)
            entries.append((k, v))
        return x, entries

    # -- the model's surfaces -------------------------------------------------

    def init_state(self, cap: int) -> dict:
        c = self.cfg
        state = self._row_state(cap)
        for l in range(self.layers):
            for kv in "kv":
                state[f"{kv}{l}"] = jnp.zeros(
                    (cap, self._positions(l), c.kv_width), c.compute_dtype)
        return state

    def step_score(self, params: dict, rows: dict, v: jax.Array,
                   live: jax.Array):
        """One event a row: the score of the bin that arrived, then the
        row's next state. The window leaves come as `ContextAtRest`s
        (scoring/stream.py): a layer appends its ONE entry a row and
        reads the table behind it in its turn, and nothing is returned
        for them. Also the step's numbers, in `step_stats`' order
        (`live` masks the padding out of them)."""
        c = self.cfg
        pos = rows["pos"]
        token, score, out = self._arrive(params, rows, v)
        x = params["embed"][token].astype(jnp.float32)
        held = busiest = one_tile = at_rest = read = jnp.zeros((), jnp.int32)
        for l in range(self.layers):
            x, counts = self._block_decode(
                l, params[f"layer{l}"], x, rows[f"k{l}"], rows[f"v{l}"], pos,
                live)
            at_rest += rows[f"k{l}"].read_rows
            read += rows[f"k{l}"].read_positions
            if counts is not None:
                held += counts.sum()
                busiest = jnp.maximum(busiest, counts.max())
                one_tile += runs_one_tile(counts)
        out["hn"] = rms(x, params["norm"], c.rms_norm_eps).astype(
            c.compute_dtype)
        n_live = live.sum()

        def mean_live(per_row):
            return (jnp.where(live, per_row, 0).sum()
                    / jnp.maximum(n_live, 1))

        # the sliding layers: positions attended to, and the rows whose
        # append overwrote an older position
        window = c.sliding_window
        attended, wrapped = (
            (mean_live(jnp.minimum(pos + 1, window)),
             (live & (pos >= window)).sum().astype(jnp.float32))
            if self.wraps else (jnp.float32(0), jnp.float32(0)))
        stats = jnp.stack([
            held.astype(jnp.float32),
            (n_live * (c.num_experts_per_tok
                       * self.dense.count(False))).astype(jnp.float32),
            busiest.astype(jnp.float32),
            mean_live(pos),
            one_tile.astype(jnp.float32),
            attended, wrapped, at_rest.astype(jnp.float32),
            read.astype(jnp.float32)])
        return score, out, stats

    def _seeded(self, name, leaf, entry, count):
        """A window leaf `[n, P, width]` after a prefill's entries `[n,
        W, width]` of which `count` a row are real. A bounded leaf
        takes them as they are; a wrapping one keeps, in slot `s`, the
        LAST position `p < count` with `p mod P == s`: what the steps
        would have left."""
        w, positions = entry.shape[1], leaf.shape[1]
        if name not in self.wraps:
            return leaf.at[:, :w].set(entry)
        slots = jnp.arange(min(w, positions))
        last = slots + positions * jnp.maximum(
            (count[:, None] - 1 - slots) // positions, 0)
        return leaf.at[:, :slots.size].set(jnp.take_along_axis(
            entry, jnp.minimum(last, w - 1)[:, :, None], axis=1))

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """State of `n` devices after their stored windows (`[n, W]`
        chronological left-padded): the prefill form over each window."""
        state, entries, count = self._warm(params, x, valid)
        for l, layer in enumerate(entries):
            for name, entry in zip((f"k{l}", f"v{l}"), layer):
                state[name] = self._seeded(name, state[name], entry, count)
        return state
