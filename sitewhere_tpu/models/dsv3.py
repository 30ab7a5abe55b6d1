"""`dsv3-stream`: a DeepSeek-V3 block stack as a streaming anomaly scorer.

A device's measurements become tokens (normalise by the device's capped
running mean and variance, the leaves and the update `lstm-stream` has,
then `bin = clip(floor((xn + 8) / 16 * V), 0, V - 1)`), the model
predicts the next bin, and an event's score is the surprisal of the bin
that arrived under the prediction made at the device's previous event:

    score_t = -log softmax(RMSNorm(h_{t-1}) W_head)[bin_t]

0 until the device has reported `max(8, window // 8)` values, clipped at
`score_clip`.

The block is the published one (config.json of deepseek-ai/DeepSeek-V3;
the configuration's keys keep their published names, so a catalog row
can be handed over as it is):

    x + MLA(RMSNorm(x)), then x + MLP(RMSNorm(x))

MLA: `cq = RMSNorm(x W_qa)`, `q = cq W_qb` -> heads of `[nope | rope]`;
`[c_kv | k_rope] = x W_kva`, `c_kv = RMSNorm(c_kv)`; rope (YaRN) on
`q_rope` and on the one shared `k_rope`. A device's context keeps
`c_kv ‖ k_rope` a position a layer. Two forms of the same numbers:
the prefill form rebuilds `[k_nope | v] = c_kv W_kvb` per head
(`_attend_prefill`: seeding, the query path); the decode form folds
W_kvb's key half into the query and applies its value half after the
weighted sum of latents, so a step works on latents alone
(`_attend_decode`: the ring step). MLP: dense in the first
`first_k_dense_replace` layers; after them one shared expert plus the
routed experts: `s = sigmoid(x W_g^T)`, choice scores `s + b`, a group's
score the sum of its two best, the `topk_group` best groups kept, the
`num_experts_per_tok` best choice scores among them, weights the chosen
`s` over their sum times `routed_scaling_factor`.

The share held here. The layer is told `(first_expert,
n_routed_experts_held)`: it routes over all `n_routed_experts`, computes
every token-expert pair that lands on a held expert (no capacity, none
dropped) and leaves out what the absent experts would add; `vocab_held`
rows of the embedding and the head are held, and the quantiser draws its
bins from them.

Multi-token prediction (`mtp_modules`): `h' = W_eh [RMSNorm(h_t) ;
RMSNorm(Emb(x_{t+1}))]`, one block, the shared head. One event yields
one score, so the module has no place in the ring step (a departure
from a sampler that verifies drafts): it serves `forecast`, the query
path, where the main head's most likely next bin is the draft and the
module gives the bin after it.

Weights in `compute_dtype`, products in it with float32 accumulation;
router, softmax, norms, residual stream and the score in float32.

State leaves (scoring/stream.py, "Contract with the model"): `mean`,
`var` f32, `count`, `pos` i32 `[rows]`; `hn` `[rows, hidden]`, what the
head needs of the previous event (the final norm's output); and one
WINDOW leaf a layer, `ctx<l>` `[rows, context_positions, entry_width]`
(`kv_lora_rank + qk_rope_head_dim` values and zeros up to whole lane
tiles), which the step appends one position to, at each row's own `pos`,
and reads for the batch's rows. Each is handed over where it rests
(`at_rest`: scoring/stream.py, `ContextAtRest`), in its layer's turn:
the layer appends its entries, then on a TPU ONE kernel a layer reads
each row's latents in the table as both keys and values
(ops/context_kernel.py's one-table form: a row's 246 KB read once, where
XLA's gather, the decode form's own write into the gathered copy and its
second reading moved them three times); elsewhere, and at shapes the
kernel does not take, the rows are gathered and `_attend_decode` reads
them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from sitewhere_tpu.models import seqblocks
from sitewhere_tpu.models.seqblocks import (  # noqa: F401  (names kept)
    EXPERT_TILE,
    SEED_TOKENS,
    Experts,
    SeqBlocks,
    rms as _rms,
    rope as _rope,
    runs_one_tile,
)
from sitewhere_tpu.ops import context_kernel


def _yarn() -> dict:
    return {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"}


@dataclass(frozen=True)
class Dsv3Config:
    # the published config.json's keys, defaults as published
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000
    rope_scaling: dict = field(default_factory=_yarn)
    max_position_embeddings: int = 163840
    vocab_size: int = 129280
    num_nextn_predict_layers: int = 1
    ep_size: int = 1
    model_type: str = "deepseek_v3"
    # the share of a layer this chip holds (0: all of it)
    first_expert: int = 0
    n_routed_experts_held: int = 0
    vocab_held: int = 0
    mtp_modules: int = 1
    # the streaming scorer round the model
    window: int = 64              # stored values a context is seeded from
    context_positions: int = 192  # positions a device's context holds
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts_held or self.n_routed_experts

    @property
    def vocab(self) -> int:
        return self.vocab_held or self.vocab_size

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def entry_width(self) -> int:
        """A context entry as it is stored: `latent_width` values, then
        zeros up to whole 128-lane tiles (576 -> 640), so that a window
        leaf rests row-major and is appended to in place
        (scoring/stream.py, "Contract with the model")."""
        return -(-self.latent_width // 128) * 128


def rope_tables(cfg: Dsv3Config, positions: int) -> tuple:
    """(cos, sin) `[positions, qk_rope_head_dim // 2]`, YaRN as published."""
    rs = cfg.rope_scaling
    return seqblocks.rope_tables(
        positions, cfg.qk_rope_head_dim, cfg.rope_theta,
        rs if rs and rs.get("type") == "yarn" else None)


def softmax_scale(cfg: Dsv3Config) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


class Dsv3StreamModel(SeqBlocks):
    """Functional, like every model here: the instance holds the
    configuration and tables made from it, weights are passed in."""

    name = "dsv3-stream"
    streaming = True
    # the numbers `step_score` returns beside the scores, by the names
    # the session feeds the metrics registry under (`scoring.<name>`)
    step_stats = ("moe.assignments_held", "moe.assignments",
                  "moe.expert_max_tokens", "ctx.positions",
                  "moe.runs_one_tile", "ctx.at_rest")
    stat_families = (SeqBlocks.expert_stats, SeqBlocks.context_stats)

    def __init__(self, cfg: Dsv3Config = Dsv3Config()):
        for key, want in (("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                          ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                          ("attention_bias", False), ("n_shared_experts", 1),
                          ("tie_word_embeddings", False),
                          ("norm_topk_prob", True)):
            if getattr(cfg, key) != want:
                raise ValueError(f"dsv3-stream computes {key}={want!r} "
                                 f"only, not {getattr(cfg, key)!r}")
        if cfg.first_expert + cfg.experts_held > cfg.n_routed_experts:
            raise ValueError("held experts reach past n_routed_experts")
        if not cfg.window <= cfg.context_positions:
            raise ValueError("a context holds fewer positions than the "
                             "window it is seeded from")
        self.cfg = cfg
        self.experts = Experts(
            routed=cfg.n_routed_experts, held=cfg.experts_held,
            first=cfg.first_expert, per_token=cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor, scoring=cfg.scoring_func,
            groups=cfg.n_group, groups_kept=cfg.topk_group)
        self.layers = cfg.num_hidden_layers
        # state leaves that are windows -> the leaf that holds the
        # position a step appends at (scoring/stream.py)
        self.windows = {f"ctx{l}": "pos" for l in range(self.layers)}
        # ...each handed over where it rests, in its layer's turn
        self.at_rest = tuple(self.windows)
        # rows one seeding call takes (StreamingRing.load blocks by it)
        self.seed_rows = max(1, SEED_TOKENS // cfg.window)
        self._cos, self._sin = rope_tables(cfg, cfg.context_positions)
        self._scale = softmax_scale(cfg)
        self._gate = max(8, cfg.window // 8)
        # one trace and one lowering for all of a program's expert
        # layers, whose shapes are the same: traced a layer, the grouped
        # pass cost a start three seconds (PERF.md, PR 29)
        self._routed = jax.jit(self.routed)

    def _is_moe(self, layer: int) -> bool:
        return layer >= self.cfg.first_k_dense_replace

    # -- weights ------------------------------------------------------------

    def _block_shapes(self, moe: bool) -> dict:
        c = self.cfg
        h, nh = c.hidden_size, c.num_attention_heads
        w, f = c.compute_dtype, jnp.float32

        def mlp(width):
            return {"gate": ((h, width), w), "up": ((h, width), w),
                    "down": ((width, h), w)}

        block = {
            "attn_norm": ((h,), f), "mlp_norm": ((h,), f),
            "q_a": ((h, c.q_lora_rank), w), "q_a_norm": ((c.q_lora_rank,), f),
            "q_b": ((c.q_lora_rank, nh * (c.qk_nope_head_dim
                                          + c.qk_rope_head_dim)), w),
            "kv_a": ((h, c.latent_width), w),
            "kv_a_norm": ((c.kv_lora_rank,), f),
            "kv_b": ((c.kv_lora_rank, nh * (c.qk_nope_head_dim
                                            + c.v_head_dim)), w),
            "o": ((nh * c.v_head_dim, h), w)}
        if moe:
            block["router"] = {"w": ((c.n_routed_experts, h), f),
                               "bias": ((c.n_routed_experts,), f)}
            block["shared"] = mlp(c.moe_intermediate_size)
            # a leaf an expert: the step reads each where it rests
            # (sliced out of one stacked leaf, all of them were copied
            # at every step: 17 of a 69 ms step, PERF.md PR 28)
            block["experts"] = {f"e{e}": mlp(c.moe_intermediate_size)
                                for e in range(c.experts_held)}
        else:
            block["mlp"] = mlp(c.intermediate_size)
        return block

    def param_shapes(self) -> dict:
        """The checkpoint's layout: name -> (shape, dtype), nested."""
        c = self.cfg
        h, w, f = c.hidden_size, c.compute_dtype, jnp.float32
        shapes = {"embed": ((c.vocab, h), w), "norm": ((h,), f),
                  "head": ((h, c.vocab), w)}
        for l in range(self.layers):
            shapes[f"layer{l}"] = self._block_shapes(self._is_moe(l))
        for m in range(c.mtp_modules):
            shapes[f"mtp{m}"] = {
                "hnorm": ((h,), f), "enorm": ((h,), f), "norm": ((h,), f),
                "eh_proj": ((2 * h, h), w), "block": self._block_shapes(True)}
        return shapes

    # -- pieces ---------------------------------------------------------------

    def _project(self, p, h, cos, sin):
        """The MLA projections of normed tokens `h` `[..., hidden]` at
        positions whose rope tables are `cos`, `sin` `[..., rope/2]`:
        (q_nope, q_rope) `[..., heads, .]` and the context entry
        `c_kv ‖ k_rope ‖ 0` `[..., entry_width]` in the compute type."""
        c = self.cfg
        cq = _rms(self._mm(h, p["q_a"]), p["q_a_norm"], c.rms_norm_eps)
        q = self._mm(cq, p["q_b"]).reshape(
            h.shape[:-1] + (c.num_attention_heads, -1))
        q_nope, q_rope = (q[..., :c.qk_nope_head_dim],
                          q[..., c.qk_nope_head_dim:])
        q_rope = _rope(q_rope, cos[..., None, :], sin[..., None, :])
        kv = self._mm(h, p["kv_a"])
        c_kv = _rms(kv[..., :c.kv_lora_rank], p["kv_a_norm"], c.rms_norm_eps)
        k_rope = _rope(kv[..., c.kv_lora_rank:], cos, sin)
        pad = jnp.zeros(c_kv.shape[:-1] + (c.entry_width - c.latent_width,),
                        jnp.float32)
        entry = jnp.concatenate([c_kv, k_rope, pad], -1).astype(
            c.compute_dtype)
        return q_nope, q_rope, entry

    def _kv_b(self, p):
        c = self.cfg
        w = p["kv_b"].reshape(c.kv_lora_rank, c.num_attention_heads, -1)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def _attend_prefill(self, p, q_nope, q_rope, entry, count):
        """The prefill form over `[n, S]` tokens: keys and values rebuilt
        from the latents per head, causal softmax, positions at or past a
        row's `count` masked out. -> `[n, S, heads * v]`."""
        c = self.cfg
        s = entry.shape[1]
        w_k, w_v = self._kv_b(p)
        lat = entry[..., :c.kv_lora_rank]
        k_rope = entry[..., c.kv_lora_rank:c.latent_width]
        k_nope = self._ein("nsc,chd->nshd", lat, w_k)
        v = self._ein("nsc,chd->nshd", lat, w_v)
        logits = (self._ein("nqhd,nkhd->nhqk", q_nope, k_nope)
                  + self._ein("nqhd,nkd->nhqk", q_rope, k_rope)) * self._scale
        at = jnp.arange(s)
        seen = (at[None, :] <= at[:, None])[None] \
            & (at[None, None, :] < jnp.maximum(count, 1)[:, None, None])
        probs = jax.nn.softmax(
            jnp.where(seen[:, None], logits, -jnp.inf), axis=-1)
        out = self._ein("nhqk,nkhd->nqhd", probs, v)
        return out.reshape(out.shape[:2] + (-1,))

    def _latent_query(self, p, q_nope, q_rope):
        """The decode form's query `[B, heads, entry_width]`: W_kvb's key
        half folded into `q_nope`, then `q_rope`, then zeros, as an entry
        rests."""
        c = self.cfg
        w_k, _ = self._kv_b(p)
        return jnp.concatenate([
            self._ein("bhd,chd->bhc", q_nope, w_k), q_rope,
            jnp.zeros(q_rope.shape[:-1] + (c.entry_width - c.latent_width,),
                      jnp.float32)], -1)

    def _latent_out(self, p, lat):
        """The weighted sum of latents `[B, heads, kv_lora_rank]` through
        W_kvb's value half. -> `[B, heads * v]`."""
        _, w_v = self._kv_b(p)
        out = self._ein("bhc,chd->bhd", lat, w_v)
        return out.reshape(out.shape[0], -1)

    def _attend_decode(self, p, q_nope, q_rope, entry, ctx, pos):
        """The decode form for one token a row: `ctx` `[B, P, latent]` is
        the row's stored context, `entry` its own position's, at `pos`.
        W_kvb's key half goes into the query, its value half comes after
        the weighted sum of latents. -> `[B, heads * v]`."""
        c = self.cfg
        rows = jnp.arange(ctx.shape[0])
        keys = ctx.at[rows, pos].set(entry, mode="drop")
        q = self._latent_query(p, q_nope, q_rope)
        logits = self._ein("bhc,bpc->bhp", q, keys) * self._scale
        seen = jnp.arange(ctx.shape[1])[None, :] <= pos[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, :], logits, -jnp.inf), axis=-1)
        lat = self._ein("bhp,bpc->bhc", probs, keys[..., :c.kv_lora_rank])
        return self._latent_out(p, lat)

    def _attend_at_rest(self, p, q_nope, q_rope, entry, ctx, pos):
        """`_attend_decode` over a context that stays in the ring's table:
        `ctx` is the layer's window leaf as the ring hands it over
        (scoring/stream.py, `ContextAtRest`). On a TPU, in bfloat16 and
        at shapes it takes, the position's own entry is appended first
        and ONE kernel reads each row's latents where they then rest, as
        keys and as values (ops/context_kernel.py, its one-table form):
        the same lines, no gathered copy. Elsewhere the rows are
        gathered, `_attend_decode` reads them and the entries are
        appended. `ctx.read_rows` is left saying how many live rows were
        read at rest. -> `[B, heads * v]`."""
        c = self.cfg
        dev, slot = ctx.dev, ctx.slot

        def plain(table, q_nope, q_rope, entry):
            rest = type(ctx)(table, dev, slot)
            out = self._attend_decode(p, q_nope, q_rope, entry, rest.rows(),
                                      pos)
            return rest.append(entry), out, jnp.int32(0)

        def rested(table, q_nope, q_rope, entry):
            table = type(ctx)(table, dev, slot).append(entry)
            q = self._latent_query(p, q_nope, q_rope).astype(c.compute_dtype)
            lat = context_kernel.context_rows(
                table, None, dev, pos, q, scale=self._scale,
                value_width=c.kv_lora_rank)
            return (table, self._latent_out(p, lat),
                    (dev < table.shape[0] - 1).sum(dtype=jnp.int32))

        args = (ctx.table, q_nope, q_rope, entry)
        if (jnp.dtype(c.compute_dtype) != jnp.bfloat16
                or not context_kernel.fits_latent(
                    ctx.table.shape, ctx.table.dtype, c.num_attention_heads,
                    c.kv_lora_rank)):
            took = plain(*args)
        else:
            took = jax.lax.platform_dependent(*args, default=plain,
                                              tpu=rested)
        ctx.table, out, ctx.read_rows = took
        return out

    def _block_prefill(self, p, x, count, cos, sin):
        """One block over `[n, S, hidden]`; also the layer's context
        entries `[n, S, entry_width]`."""
        c = self.cfg
        n, s, hid = x.shape
        with jax.named_scope("mla_project"):
            q_nope, q_rope, entry = self._project(
                p, _rms(x, p["attn_norm"], c.rms_norm_eps), cos, sin)
        with jax.named_scope("mla_attend"):
            x = x + self._mm(self._attend_prefill(
                p, q_nope, q_rope, entry, count), p["o"])
        flat = _rms(x, p["mlp_norm"], c.rms_norm_eps).reshape(n * s, hid)
        y, _ = self._ffn(p, flat, jnp.ones(n * s, bool))
        return x + y.reshape(n, s, hid), entry

    def _block_decode(self, p, x, ctx, pos, live):
        c = self.cfg
        at = jnp.minimum(pos, c.context_positions - 1)
        cos, sin = jnp.asarray(self._cos)[at], jnp.asarray(self._sin)[at]
        with jax.named_scope("mla_project"):
            q_nope, q_rope, entry = self._project(
                p, _rms(x, p["attn_norm"], c.rms_norm_eps), cos, sin)
        with jax.named_scope("mla_attend"):
            x = x + self._mm(self._attend_at_rest(
                p, q_nope, q_rope, entry, ctx, pos), p["o"])
        y, counts = self._ffn(p, _rms(x, p["mlp_norm"], c.rms_norm_eps), live)
        return x + y, counts

    # -- tokens -------------------------------------------------------------

    def _prefill(self, params, tokens, count):
        """Every block over `[n, S]` tokens: (hidden states before the
        final norm `[n, S, hidden]`, context entries a layer)."""
        s = tokens.shape[1]
        cos, sin = jnp.asarray(self._cos)[:s], jnp.asarray(self._sin)[:s]
        x = params["embed"][tokens].astype(jnp.float32)
        entries = []
        for l in range(self.layers):
            x, entry = self._block_prefill(params[f"layer{l}"], x, count,
                                           cos, sin)
            entries.append(entry)
        return x, entries

    # -- the model's surfaces -------------------------------------------------

    def init_state(self, cap: int) -> dict:
        c = self.cfg
        state = self._row_state(cap)
        for l in range(self.layers):
            state[f"ctx{l}"] = jnp.zeros(
                (cap, c.context_positions, c.entry_width), c.compute_dtype)
        return state

    def step_score(self, params: dict, rows: dict, v: jax.Array,
                   live: jax.Array):
        """One event a row: the score of the bin that arrived, then the
        row's next state. The window leaves come as `ContextAtRest`s: a
        layer appends its ONE entry a row at `rows["pos"]` and reads the
        table behind it (`_attend_at_rest`); nothing is returned for
        them. Also the step's numbers, in `step_stats`' order (`live`
        masks the padding out of them)."""
        c = self.cfg
        pos = rows["pos"]
        token, score, out = self._arrive(params, rows, v)
        x = params["embed"][token].astype(jnp.float32)
        held = busiest = one_tile = at_rest = jnp.zeros((), jnp.int32)
        for l in range(self.layers):
            x, counts = self._block_decode(
                params[f"layer{l}"], x, rows[f"ctx{l}"], pos, live)
            at_rest += rows[f"ctx{l}"].read_rows
            if counts is not None:
                held += counts.sum()
                busiest = jnp.maximum(busiest, counts.max())
                one_tile += runs_one_tile(counts)
        out["hn"] = _rms(x, params["norm"], c.rms_norm_eps).astype(
            c.compute_dtype)
        n_live = live.sum()
        n_moe = sum(self._is_moe(l) for l in range(self.layers))
        stats = jnp.stack([
            held.astype(jnp.float32),
            (n_live * (c.num_experts_per_tok * n_moe)).astype(jnp.float32),
            busiest.astype(jnp.float32),
            jnp.where(live, pos, 0).sum() / jnp.maximum(n_live, 1),
            one_tile.astype(jnp.float32),
            at_rest.astype(jnp.float32)])
        return score, out, stats

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """State of `n` devices after their stored windows (`[n, W]`
        chronological left-padded): the prefill form over each window."""
        state, entries, _ = self._warm(params, x, valid)
        for l, entry in enumerate(entries):
            state[f"ctx{l}"] = state[f"ctx{l}"].at[:, :x.shape[1]].set(entry)
        return state

    def forecast_bins(self, params: dict, x: jax.Array, valid: jax.Array):
        """(draft `[n]`, log-probabilities of the bin after it `[n, V]`):
        the main head's most likely next bin, and the MTP module's
        distribution of the one after (the main head's own where the
        configuration holds no module)."""
        c = self.cfg
        n = x.shape[0]
        tokens, count, _, _ = self._window_tokens(x, valid)
        h, _ = self._prefill(params, tokens, count)
        last = jnp.maximum(count - 1, 0)
        rows = jnp.arange(n)
        logits = self._logits(params, h[rows, last])
        draft = jnp.argmax(logits, -1).astype(jnp.int32)
        if not c.mtp_modules:
            return draft, jax.nn.log_softmax(logits, -1)
        p = params["mtp0"]
        s = tokens.shape[1]
        following = jnp.roll(tokens, -1, axis=1).at[rows, last].set(draft)
        joined = jnp.concatenate([
            _rms(h, p["hnorm"], c.rms_norm_eps),
            _rms(params["embed"][following].astype(jnp.float32), p["enorm"],
                 c.rms_norm_eps)], -1)
        cos, sin = jnp.asarray(self._cos)[:s], jnp.asarray(self._sin)[:s]
        h2, _ = self._block_prefill(p["block"], self._mm(joined, p["eh_proj"]),
                                    count, cos, sin)
        with jax.named_scope("lm_head"):
            after = self._mm(_rms(h2[rows, last], p["norm"], c.rms_norm_eps),
                             params["head"])
        return draft, jax.nn.log_softmax(after, -1)

    def forecast(self, params: dict, x: jax.Array,
                 valid: jax.Array) -> jax.Array:
        """Two steps ahead in ORIGINAL units, `[n, 2, 1]`: the centre of
        the draft bin, then of the MTP module's most likely bin."""
        _, _, mean, var = self._window_tokens(x, valid)
        sd = jnp.sqrt(var + 1e-6)
        draft, after = self.forecast_bins(params, x, valid)
        bins = jnp.stack([draft, jnp.argmax(after, -1)], 1)
        xn = (bins + 0.5) * (16.0 / self.cfg.vocab) - 8.0
        return (xn * sd[:, None] + mean[:, None])[..., None]
