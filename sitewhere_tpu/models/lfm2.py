"""`lfm2-stream`: an LFM2-24B-A2B block stack as a streaming anomaly
scorer (tokens, score and gate as models/seqblocks.py has them).

The block is the published one (config.json of LiquidAI/LFM2-24B-A2B,
`model_type` `lfm2_moe`; the configuration's keys keep their published
names, so a catalog row can be handed over as it is). A layer is `x <-
x + op(RMSNorm(x))`, then `x <- x + ffn(RMSNorm(x))`, every projection
without bias; after the last layer one more RMSNorm, then the head,
which is the embedding's matrix (`tie_embedding`). Layer `l`'s operator
is of one of two kinds (`layer_types[l]`), on the normed input `u`:

`conv`, the family's double-gated short convolution, one event `t`:

    (B, C, z) = split3(u W_in);  s_t = B * z
    c_t = sum_j w[j] * s_{t - K + 1 + j},  K = conv_L_cache taps, causal,
          depthwise, zeros before the device's first event, no bias
    op = (C * c_t) W_out

No activation. What a device keeps is `s_{t-K+1} .. s_{t-1}`, the last
`K - 1` inputs of the taps. `s` is rounded to the type it rests in
before any tap reads it, this event's too, so an input is the same
number at each of the `K` events that read it.

`full_attention`: `q = u Wq` as `num_attention_heads` heads of `d =
hidden_size / num_attention_heads`, `k = u Wk`, `v = u Wv` as
`num_key_value_heads` of `d`; `q` and `k` each through an RMSNorm over
the head's `d` (one learned weight for queries, one for keys), then the
rotary turn at the device's position over all `d` dimensions, pairs `(i,
i + d / 2)`; `softmax(q K^T / sqrt(d)) V` over every position `j <= t`,
query head `h` on key-value head `h // (heads / kv)`; `Wo`.

`ffn`: the first `num_dense_layers` layers a SiLU-gated MLP of
`intermediate_size`; the others `s = sigmoid(n Wr^T)` over `num_experts`
in float32, the `num_experts_per_tok` largest of `s + b` (the selection
bias `b` takes part in the choice only; none where not
`use_expert_bias`), weights the chosen `s` over (their sum + 1e-6)
(`norm_topk_prob`) times `routed_scaling_factor`, the sum of weight
times the expert's SiLU-gated MLP of `moe_intermediate_size`. No shared
expert.

What the config leaves open is set by the family's convention (the
benchmark's configuration lists each under `assumed`).

The share held here: `(first_expert, num_experts_held)` of each expert
layer (models/seqblocks.py, `Experts`); by default all of them.

Weights in `compute_dtype`, matrix products in it with float32
accumulation; router, sigmoid, softmax, norms, the gates `B * z` and `C
* c`, the taps' sum, residual stream and score in float32.

State leaves (scoring/stream.py, "Contract with the model"): `mean`,
`var` f32, `count`, `pos` i32 `[rows]`; `hn` `[rows, hidden]`; a conv
layer's `c<l>` `[rows, (K - 1) * hidden / 128, 128]`, the taps' last `K
- 1` inputs, oldest first, in whole lane tiles (handed over in turn, a
`RowsInTurn`); an attention layer's `k<l>`, `v<l>` `[rows,
context_positions, kv * d]`, the only window leaves, bounded, and read
where they rest (`at_rest`): `ops/context_kernel.py` takes a key-value
head of 64, half a lane tile, two to a tile. Rope is applied before an
entry is stored.

Two forms of the same numbers. The decode form, one event a row, reads
the two inputs before it as they rest and a context as it rests. The
prefill form (seeding, the query path) is a causal depthwise
convolution over the window, one pass and no scan, and a masked
softmax; `_window_tokens` hands it windows with the valid values first,
so a position at or past a row's `count` leaves no trace in what a row
keeps.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from sitewhere_tpu.models import seqblocks
from sitewhere_tpu.models.seqblocks import (
    SEED_TOKENS,
    Experts,
    SeqBlocks,
    normal,
    rms,
    rope_halves,
    runs_one_tile,
)

_LAYERS = 40              # the published depth
CONV, FULL = "conv", "full_attention"
ROUTER_SUM_EPS = 1e-6     # the published rule's denominator: sum + 1e-6


def _layer_types() -> list:
    """conv, conv, full_attention, then (conv, conv, conv,
    full_attention) nine times and a last conv: 30 and 10."""
    return [CONV, CONV, FULL] + [CONV, CONV, CONV, FULL] * 9 + [CONV]


@dataclass(frozen=True)
class Lfm2Config:
    # the published config.json's keys, defaults as published
    model_type: str = "lfm2_moe"
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    num_hidden_layers: int = _LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1
    rope_parameters: dict = field(default_factory=lambda: {
        "rope_theta": 1000000, "rope_type": "default"})
    layer_types: list = field(default_factory=_layer_types)
    # the family's published configs tie the head to the embedding
    tie_embedding: bool = True
    # the share of a layer this chip holds (0: all of it)
    first_expert: int = 0
    num_experts_held: int = 0
    # the streaming scorer round the model
    window: int = 96              # stored values a row is seeded from
    context_positions: int = 512  # positions an attention layer's context holds
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0

    @property
    def experts_held(self) -> int:
        return self.num_experts_held or self.num_experts

    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def rms_norm_eps(self) -> float:
        return self.norm_eps

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_width(self) -> int:
        """A position's keys (or values) as they are stored."""
        return self.num_key_value_heads * self.head_dim


class Lfm2StreamModel(SeqBlocks):
    """Functional, like every model here: the instance holds the
    configuration and tables made from it, weights are passed in."""

    name = "lfm2-stream"
    streaming = True
    # the numbers `step_score` returns beside the scores, by the names
    # the session feeds the metrics registry under (`scoring.<name>`)
    step_stats = ("moe.assignments_held", "moe.assignments",
                  "moe.expert_max_tokens", "ctx.positions",
                  "moe.runs_one_tile", "ctx.at_rest", "ctx.read_positions")
    stat_families = (SeqBlocks.expert_stats, SeqBlocks.context_stats)

    def __init__(self, cfg: Lfm2Config = Lfm2Config()):
        n = cfg.num_hidden_layers
        for key, want in (("conv_bias", False), ("tie_embedding", True)):
            if getattr(cfg, key) != want:
                raise ValueError(f"lfm2-stream computes {key}={want!r} "
                                 f"only, not {getattr(cfg, key)!r}")
        if (cfg.rope_parameters or {}).get("rope_type",
                                           "default") != "default":
            raise ValueError("lfm2-stream turns positions by plain rope")
        if len(cfg.layer_types) < n:
            raise ValueError(f"layer_types names fewer than {n} layers")
        self.kinds = list(cfg.layer_types[:n])
        if set(self.kinds) - {CONV, FULL}:
            raise ValueError("lfm2-stream: a kind of layer it cannot "
                             "compute")
        if cfg.hidden_size % cfg.num_attention_heads \
                or cfg.num_attention_heads % cfg.num_key_value_heads \
                or cfg.head_dim % 2:
            raise ValueError("hidden_size is no whole number of heads, or "
                             "the heads no whole groups of an even width")
        if cfg.first_expert + cfg.experts_held > cfg.num_experts:
            raise ValueError("held experts reach past num_experts")
        if not cfg.window <= cfg.context_positions:
            raise ValueError("a context holds fewer positions than the "
                             "window it is seeded from")
        for what, width in (
                ("a position's keys", cfg.kv_width),
                ("the taps' inputs", (cfg.conv_L_cache - 1)
                 * cfg.hidden_size)):
            if width % 128 or not width:
                raise ValueError(f"{what} are no whole lane tiles")
        self.cfg = cfg
        self.layers = n
        self.dense = [l < cfg.num_dense_layers for l in range(n)]
        self.experts = Experts(
            routed=cfg.num_experts, held=cfg.experts_held,
            first=cfg.first_expert, per_token=cfg.num_experts_per_tok,
            scale=float(cfg.routed_scaling_factor), scoring="sigmoid",
            normed=cfg.norm_topk_prob, sum_eps=ROUTER_SUM_EPS)
        # a row of a conv layer's leaf (`init_state`)
        self._taps_shape = ((cfg.conv_L_cache - 1) * cfg.hidden_size // 128,
                            128)
        # state leaves that are windows -> the leaf that holds the
        # position a step appends at (scoring/stream.py); the conv
        # layers' leaves are rows, rewritten whole
        self.windows = {f"{kv}{l}": "pos" for l in range(n)
                        if self.kinds[l] == FULL for kv in "kv"}
        # ...each handed over where it rests, in its layer's turn
        self.at_rest = tuple(self.windows)
        # rows one seeding call takes (StreamingRing.load blocks by it)
        self.seed_rows = max(1, SEED_TOKENS // cfg.window)
        self._gate = max(8, cfg.window // 8)
        self._scale = cfg.head_dim ** -0.5
        # the turn's tables over every position a context can reach
        self._cos, self._sin = seqblocks.rope_tables(
            cfg.context_positions, cfg.head_dim,
            cfg.rope_parameters["rope_theta"])
        # one trace and one lowering for all of a program's expert
        # layers, whose shapes are the same (models/dsv3.py)
        self._routed = jax.jit(self.routed)

    # -- weights ------------------------------------------------------------

    def _block_shapes(self, layer: int) -> dict:
        c = self.cfg
        h, w, f = c.hidden_size, c.compute_dtype, jnp.float32

        def mlp(width):
            return {"gate": ((h, width), w), "up": ((h, width), w),
                    "down": ((width, h), w)}

        block = {"op_norm": ((h,), f), "ffn_norm": ((h,), f)}
        if self.kinds[layer] == CONV:
            block.update({"in": ((h, 3 * h), w),
                          "conv": ((c.conv_L_cache, h), w),
                          "out": ((h, h), w)})
        else:
            block.update({"q": ((h, h), w), "k": ((h, c.kv_width), w),
                          "v": ((h, c.kv_width), w), "o": ((h, h), w),
                          "q_norm": ((c.head_dim,), f),
                          "k_norm": ((c.head_dim,), f)})
        if self.dense[layer]:
            block["mlp"] = mlp(c.intermediate_size)
        else:
            block["router"] = {"w": ((c.num_experts, h), f)}
            if c.use_expert_bias:
                block["router"]["bias"] = ((c.num_experts,), f)
            # a leaf an expert: the step reads each where it rests
            block["experts"] = {f"e{e}": mlp(c.moe_intermediate_size)
                                for e in range(c.experts_held)}
        return block

    def param_shapes(self) -> dict:
        """The checkpoint's layout: name -> (shape, dtype), nested. One
        matrix is the embedding and the head."""
        c = self.cfg
        shapes = {"embed": ((c.vocab, c.hidden_size), c.compute_dtype),
                  "norm": ((c.hidden_size,), jnp.float32)}
        for l in range(self.layers):
            shapes[f"layer{l}"] = self._block_shapes(l)
        return shapes

    def init(self, rng: jax.Array) -> dict:
        """`SeqBlocks.init`'s weights, and a conv layer's taps drawn so
        that `c_t` keeps `s_t`'s scale: normal, std `K ** -0.5`."""
        params = super().init(rng)
        c = self.cfg
        for l in range(self.layers):
            if self.kinds[l] == CONV:
                params[f"layer{l}"]["conv"] = normal(
                    jax.random.fold_in(rng, 1 << 20 | l),
                    (c.conv_L_cache, c.hidden_size), c.compute_dtype,
                    c.conv_L_cache ** -0.5)
        return params

    # -- the conv operator ----------------------------------------------------

    def _conv_project(self, p, u):
        """`(s, C)` of normed tokens `u` `[..., hidden]`: the taps'
        input `B * z` as it rests, and the output's gate, float32."""
        h = self.cfg.hidden_size
        with jax.named_scope("conv_project"):
            bcz = self._mm(u, p["in"])
            s = (bcz[..., :h] * bcz[..., 2 * h:]).astype(
                self.cfg.compute_dtype)
            return s, bcz[..., h:2 * h]

    def _conv_out(self, p, x, gate, c_t):
        with jax.named_scope("conv_out"):
            return x + self._mm(gate * c_t, p["out"])

    def _conv_decode(self, p, x, taps):
        """A conv layer's operator on `x` `[B, hidden]`, one event a
        row; `taps` is the layer's leaf in turn (scoring/stream.py,
        `RowsInTurn`): read when the layer starts, written whole before
        the next one starts."""
        c = self.cfg
        h, k = c.hidden_size, c.conv_L_cache
        s, gate = self._conv_project(p, rms(x, p["op_norm"], c.rms_norm_eps))
        with jax.named_scope("conv_taps"):
            wide = jnp.concatenate(
                [taps.read(x).reshape(x.shape[0], -1), s], -1)
            w = p["conv"].astype(jnp.float32)
            c_t = sum(wide[:, j * h:(j + 1) * h].astype(jnp.float32) * w[j]
                      for j in range(k))
            kept = wide[:, h:].reshape((-1,) + self._taps_shape)
        return taps.write(kept, self._conv_out(p, x, gate, c_t))

    def _conv_prefill(self, p, x, count):
        """Over `[n, S, hidden]`: a causal depthwise convolution of the
        window, one pass. -> (x, what a row keeps after position `count
        - 1`: its last `K - 1` inputs `[n, (K - 1) * hidden]`, zeros
        where it has had fewer)."""
        c = self.cfg
        n, s_len, h = x.shape
        k = c.conv_L_cache
        s, gate = self._conv_project(p, rms(x, p["op_norm"], c.rms_norm_eps))
        with jax.named_scope("conv_taps"):
            padded = jnp.pad(s, ((0, 0), (k - 1, 0), (0, 0)))
            w = p["conv"].astype(jnp.float32)
            c_t = sum(padded[:, j:j + s_len].astype(jnp.float32) * w[j]
                      for j in range(k))
            # position `p` rests at `p + K - 1` of the padded window
            last = count[:, None] + jnp.arange(k - 1)[None, :]
            kept = jnp.take_along_axis(padded, last[:, :, None], axis=1)
        return self._conv_out(p, x, gate, c_t), kept.reshape(n, -1)

    # -- attention ------------------------------------------------------------

    def _project(self, p, u, at):
        """Queries `[..., heads, d]` and keys `[..., kv * d]` of normed
        tokens `u` `[..., hidden]` at positions `at` `[...]`, each head
        normed and turned, and values `[..., kv * d]`; keys and values
        as they rest."""
        c = self.cfg
        d, cdt = c.head_dim, c.compute_dtype
        cos = jnp.asarray(self._cos)[at][..., None, :]
        sin = jnp.asarray(self._sin)[at][..., None, :]

        def heads(x, norm):
            x = x.reshape(x.shape[:-1] + (-1, d))
            return rope_halves(rms(x, norm, c.rms_norm_eps), cos, sin)

        q = heads(self._mm(u, p["q"]), p["q_norm"])
        k = heads(self._mm(u, p["k"]), p["k_norm"])
        return (q, k.reshape(k.shape[:-2] + (-1,)).astype(cdt),
                self._mm(u, p["v"]).astype(cdt))

    def _attention(self, p, x, at, attend):
        """An attention layer's operator on the residual stream `x`
        `[..., hidden]` at positions `at`; `attend(q, k, v)` is the
        form. -> (x, the stored keys, the stored values)."""
        c = self.cfg
        u = rms(x, p["op_norm"], c.rms_norm_eps)
        with jax.named_scope("gqa_project"):
            q, k, v = self._project(p, u, at)
        with jax.named_scope("attn_full"):
            a = attend(q, k, v)
            return x + self._mm(a.reshape(x.shape), p["o"]), k, v

    # -- the block --------------------------------------------------------------

    def _ffn_half(self, p, x, live):
        y, counts = self._ffn(p, rms(x, p["ffn_norm"], self.cfg.rms_norm_eps),
                              live)
        return x + y, counts

    def _prefill(self, params, tokens, count):
        """Every block over `[n, S]` tokens: (hidden states before the
        final norm `[n, S, hidden]`, what a layer leaves a row: a conv
        layer its taps' last inputs after position `count - 1`, an
        attention layer its keys and values `[n, S, kv * d]`)."""
        c = self.cfg
        n, s_len = tokens.shape
        x = params["embed"][tokens].astype(jnp.float32)
        left = []
        for l in range(self.layers):
            p = params[f"layer{l}"]
            if self.kinds[l] == CONV:
                x, *rest = self._conv_prefill(p, x, count)
            else:
                x, *rest = self._attention(
                    p, x, jnp.arange(s_len),
                    lambda q, k, v: self._causal_prefill(
                        q, k, v, count, c.num_key_value_heads))
            left.append(rest)
            flat, _ = self._ffn_half(p, x.reshape(n * s_len, -1),
                                     jnp.ones(n * s_len, bool))
            x = flat.reshape(x.shape)
        return x, left

    # -- the model's surfaces -------------------------------------------------

    def _leaves(self, layer: int) -> tuple:
        """The names of the leaves a layer keeps a row."""
        return ((f"c{layer}",) if self.kinds[layer] == CONV
                else (f"k{layer}", f"v{layer}"))

    def init_state(self, cap: int) -> dict:
        c = self.cfg
        state = self._row_state(cap)
        for l in range(self.layers):
            for name in self._leaves(l):
                state[name] = jnp.zeros(
                    (cap,) + (self._taps_shape if self.kinds[l] == CONV
                              else (c.context_positions, c.kv_width)),
                    c.compute_dtype)
        return state

    def step_score(self, params: dict, rows: dict, v: jax.Array,
                   live: jax.Array):
        """One event a row: the score of the bin that arrived, then the
        row's next state. A conv layer's `c` comes in turn
        (scoring/stream.py, `RowsInTurn`), an attention layer's window
        leaves as `ContextAtRest`s: the layer appends its ONE entry a
        row and reads the table behind it (`_decode_at_rest`); nothing
        is returned for either. Also the step's numbers, in
        `step_stats`' order (`live` masks the padding out of them)."""
        c = self.cfg
        pos = rows["pos"]
        token, score, out = self._arrive(params, rows, v)
        x = params["embed"][token].astype(jnp.float32)
        held = busiest = one_tile = at_rest = read = jnp.zeros((), jnp.int32)
        for l in range(self.layers):
            p = params[f"layer{l}"]
            if self.kinds[l] == CONV:
                x = self._conv_decode(p, x, rows[f"c{l}"])
            else:
                kctx, vctx = rows[f"k{l}"], rows[f"v{l}"]
                x, _, _ = self._attention(
                    p, x, jnp.minimum(pos, c.context_positions - 1),
                    lambda q, k, v, kctx=kctx, vctx=vctx:
                    self._decode_at_rest(q, k, v, kctx, vctx, pos,
                                         c.num_key_value_heads))
                at_rest += kctx.read_rows
                read += kctx.read_positions
            x, counts = self._ffn_half(p, x, live)
            if counts is not None:
                held += counts.sum()
                busiest = jnp.maximum(busiest, counts.max())
                one_tile += runs_one_tile(counts)
        out["hn"] = rms(x, params["norm"], c.rms_norm_eps).astype(
            c.compute_dtype)
        n_live = live.sum()
        stats = jnp.stack([
            held.astype(jnp.float32),
            (n_live * (c.num_experts_per_tok
                       * self.dense.count(False))).astype(jnp.float32),
            busiest.astype(jnp.float32),
            jnp.where(live, pos, 0).sum() / jnp.maximum(n_live, 1),
            one_tile.astype(jnp.float32),
            at_rest.astype(jnp.float32), read.astype(jnp.float32)])
        return score, out, stats

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """State of `n` devices after their stored windows (`[n, W]`
        chronological left-padded): the prefill form over each window."""
        state, left, _ = self._warm(params, x, valid)
        w = x.shape[1]
        for l, rest in enumerate(left):
            for name, entry in zip(self._leaves(l), rest):
                state[name] = (
                    entry.reshape(state[name].shape)
                    if self.kinds[l] == CONV
                    else state[name].at[:, :w].set(entry))
        return state
