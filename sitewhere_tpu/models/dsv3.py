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
tiles), which the step reads for the batch's rows and appends one
position to, at each row's own `pos`.
"""

from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.ops import expert_kernel

EXPERT_TILE = 128        # rows of one held expert's products at a time
SEED_TOKENS = 2048       # tokens of one seeding call: its activations
                         # (under 1 GB at the published widths) fit
                         # beside the weights and the context


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


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_tables(cfg: Dsv3Config, positions: int) -> tuple:
    """(cos, sin) `[positions, qk_rope_head_dim // 2]`, YaRN as published:
    frequencies above the correction range keep theta's, those below are
    divided by `factor`, a linear ramp between."""
    rs, dim, base = cfg.rope_scaling, cfg.qk_rope_head_dim, cfg.rope_theta
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rs and rs.get("type") == "yarn":
        orig = rs["original_max_position_embeddings"]

        def correction(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction(rs["beta_fast"])), 0)
        high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        freq = freq / rs["factor"] * ramp + freq * (1.0 - ramp)
    angle = np.arange(positions, dtype=np.float64)[:, None] * freq[None, :]
    return (np.cos(angle).astype(np.float32),
            np.sin(angle).astype(np.float32))


def softmax_scale(cfg: Dsv3Config) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


def _rope(x, cos, sin):
    """Rotate pairs `(2i, 2i + 1)` of the last axis; `cos`, `sin`
    broadcast against `x[..., ::2]`."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


class Dsv3StreamModel:
    """Functional, like every model here: the instance holds the
    configuration and tables made from it, weights are passed in."""

    name = "dsv3-stream"
    streaming = True
    # the numbers `step_score` returns beside the scores, by the names
    # the session feeds the metrics registry under (`scoring.<name>`)
    step_stats = ("moe.assignments_held", "moe.assignments",
                  "moe.expert_max_tokens", "ctx.positions",
                  "moe.runs_one_tile")

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
        self.layers = cfg.num_hidden_layers
        # state leaves that are windows -> the leaf that holds the
        # position a step appends at (scoring/stream.py)
        self.windows = {f"ctx{l}": "pos" for l in range(self.layers)}
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

    def init(self, rng: jax.Array) -> dict:
        """Random weights leaf by leaf (normal, std 0.02; norms 1; the
        router's bias std 0.01), each made in float32 and kept in its own
        type, so that no second copy of the set is ever alive."""
        made = itertools.count()

        def build(spec, name=""):
            if isinstance(spec, dict):
                return {k: build(v, k) for k, v in spec.items()}
            shape, dtype = spec
            if "norm" in name:
                return jnp.ones(shape, dtype)
            return _normal(jax.random.fold_in(rng, next(made)), shape, dtype,
                           0.01 if name == "bias" else 0.02)

        return build(self.param_shapes())

    # -- pieces ---------------------------------------------------------------

    def _mm(self, x, w):
        cdt = self.cfg.compute_dtype
        return jnp.dot(x.astype(cdt), w.astype(cdt),
                       preferred_element_type=jnp.float32,
                       precision=_precision(cdt))

    def _ein(self, spec, a, b):
        cdt = self.cfg.compute_dtype
        return jnp.einsum(spec, a.astype(cdt), b.astype(cdt),
                          preferred_element_type=jnp.float32,
                          precision=_precision(cdt))

    def _mlp(self, p, x):
        return self._mm(jax.nn.silu(self._mm(x, p["gate"]))
                        * self._mm(x, p["up"]), p["down"])

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

    def _attend_decode(self, p, q_nope, q_rope, entry, ctx, pos):
        """The decode form for one token a row: `ctx` `[B, P, latent]` is
        the row's stored context, `entry` its own position's, at `pos`.
        W_kvb's key half goes into the query, its value half comes after
        the weighted sum of latents. -> `[B, heads * v]`."""
        c = self.cfg
        w_k, w_v = self._kv_b(p)
        rows = jnp.arange(ctx.shape[0])
        keys = ctx.at[rows, pos].set(entry, mode="drop")
        q = jnp.concatenate([
            self._ein("bhd,chd->bhc", q_nope, w_k), q_rope,
            jnp.zeros(q_rope.shape[:-1] + (c.entry_width - c.latent_width,),
                      jnp.float32)], -1)
        logits = self._ein("bhc,bpc->bhp", q, keys) * self._scale
        seen = jnp.arange(ctx.shape[1])[None, :] <= pos[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, :], logits, -jnp.inf), axis=-1)
        lat = self._ein("bhp,bpc->bhc", probs, keys[..., :c.kv_lora_rank])
        out = self._ein("bhc,chd->bhd", lat, w_v)
        return out.reshape(out.shape[0], -1)

    def route(self, p, x):
        """Experts and weights of tokens `x` `[T, hidden]` (float32):
        (`[T, k]` int32, `[T, k]` float32), over ALL routed experts."""
        c = self.cfg
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), p["w"].T,
            precision=jax.lax.Precision.HIGHEST))
        choice = s + p["bias"]
        groups = choice.reshape(x.shape[0], c.n_group, -1)
        group_score = jax.lax.top_k(groups, 2)[0].sum(-1)
        kept = jax.lax.top_k(group_score, c.topk_group)[1]
        keep = jnp.zeros((x.shape[0], c.n_group), bool).at[
            jnp.arange(x.shape[0])[:, None], kept].set(True)
        masked = jnp.where(jnp.repeat(keep, groups.shape[-1], axis=1),
                           choice, -jnp.inf)
        idx = jax.lax.top_k(masked, c.num_experts_per_tok)[1]
        w = jnp.take_along_axis(s, idx, axis=1)
        w = w / w.sum(-1, keepdims=True) * c.routed_scaling_factor
        return idx.astype(jnp.int32), w

    def routed(self, p, x, idx, w, live, tile=EXPERT_TILE):
        """What the held experts give for tokens `x` `[T, hidden]`:
        `sum_k w * expert_k(x)` over the chosen experts held here, and
        each held expert's token count `[held]` (rows not `live` count
        and compute nothing). The pairs are sorted by expert and the
        layer is ONE grouped pass over them: the token rows of every
        held expert's first `tile` pairs are gathered once, laid at
        `e * tile`, each expert's three products run over its tile with
        no loop round them (an expert's leaves are read once, one after
        another), and the weighted rows are summed into the tokens. A
        run longer than `tile` takes its further tiles in a loop that
        is entered only where some run is that long: nothing is
        dropped, no expert has a capacity."""
        c = self.cfg
        t, k = idx.shape
        held = c.experts_held
        local = idx.reshape(-1) - c.first_expert
        here = (local >= 0) & (local < held) & jnp.repeat(live, k)
        group = jnp.where(here, local, held)
        _, token, weight = jax.lax.sort(
            (group, jnp.arange(t * k, dtype=jnp.int32) // k, w.reshape(-1)),
            num_keys=1, is_stable=True)
        counts = (group[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)
        starts = jnp.cumsum(counts) - counts
        token = jnp.concatenate([token, jnp.zeros(tile, jnp.int32)])
        weight = jnp.concatenate([weight, jnp.zeros(tile, jnp.float32)])
        xc = x.astype(c.compute_dtype)
        lane = jnp.arange(tile)

        def tile_of(lo, end):
            """Token rows and weights of the `tile` pairs from `lo` on
            (weight 0 from `end` on), for one run or `[held]` runs."""
            at = lo[..., None] + lane
            return token[at], jnp.where(at < end[..., None], weight[at], 0.0)

        rows, wt = tile_of(starts, starts + counts)
        out = self._first_tiles([p[f"e{e}"] for e in range(held)], xc,
                                rows.reshape(-1), wt.reshape(-1), counts)

        def further_tiles(out):
            for e in range(held):
                start, end = starts[e], starts[e] + counts[e]
                expert = p[f"e{e}"]

                def further(i, out, start=start, end=end, expert=expert):
                    rows, wt = tile_of(start + i * tile, end)
                    return out.at[rows].add(
                        self._mlp(expert, xc[rows]) * wt[:, None])

                out = jax.lax.fori_loop(1, (counts[e] + tile - 1) // tile,
                                        further, out)
            return out

        return jax.lax.cond((counts > tile).any(), further_tiles,
                            lambda out: out, out), counts

    def _first_tiles(self, experts, xc, rows, wt, counts):
        """`sum w * expert(x)` over every held expert's first tile of
        pairs: `rows`, `wt` `[held * tile]` are the pairs' tokens and
        weights (0 past a run's end), tile `e` expert `e`'s. ->
        `[T, hidden]` float32. On a TPU, in bfloat16 and at shapes it
        takes, one kernel that streams the leaves where they rest and
        sums in place (ops/expert_kernel.py); elsewhere the same three
        products and a scatter-add an expert."""
        t, tile = xc.shape[0], rows.shape[0] // len(experts)

        def plain(experts, xs, rows, wt, counts):
            # a scatter-add an expert: one of every tile's rows at once
            # took twice their time on a v5e (PERF.md, PR 29); `counts`
            # is for the kernel, here `wt` is 0 past a run's end
            out = jnp.zeros((t, xs.shape[1]), jnp.float32)
            for e, expert in enumerate(experts):
                at = slice(e * tile, (e + 1) * tile)
                out = out.at[rows[at]].add(
                    self._mlp(expert, xs[at]) * wt[at, None])
            return out

        hidden, inter = experts[0]["gate"].shape
        if (jnp.dtype(self.cfg.compute_dtype) != jnp.bfloat16
                or not expert_kernel.fits(t, hidden, inter, tile)):
            return plain(experts, xc[rows], rows, wt, counts)
        return jax.lax.platform_dependent(
            experts, xc[rows], rows, wt, counts, default=plain,
            tpu=functools.partial(expert_kernel.expert_tiles, tokens=t))

    def _ffn(self, p, x, live):
        """The block's second half on normed tokens `[T, hidden]`; the
        held experts' token counts `[held]` where the layer has experts."""
        if "mlp" in p:
            with jax.named_scope("dense_mlp"):
                return self._mlp(p["mlp"], x), None
        with jax.named_scope("moe_route"):
            idx, w = self.route(p["router"], x)
        with jax.named_scope("moe_experts"):
            routed, counts = self._routed(p["experts"], x, idx, w, live)
            return self._mlp(p["shared"], x) + routed, counts

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
            x = x + self._mm(self._attend_decode(
                p, q_nope, q_rope, entry, ctx, pos), p["o"])
        y, counts = self._ffn(p, _rms(x, p["mlp_norm"], c.rms_norm_eps), live)
        return x + y, entry, counts

    # -- tokens -------------------------------------------------------------

    def _bin(self, xn):
        v = self.cfg.vocab
        return jnp.clip(jnp.floor((xn + 8.0) / 16.0 * v), 0,
                        v - 1).astype(jnp.int32)

    def _window_tokens(self, x, valid):
        """A stored window `[n, W]` (chronological, left-padded) as
        tokens with the valid ones first, and the window's statistics:
        (tokens `[n, W]`, count `[n]`, mean `[n]`, var `[n]`). The
        statistics are taken value by value in stored order, by the rule
        an event updates them with: no sum, so no order of summation
        for another program to disagree about at a bin's edge."""
        n, w = x.shape

        def take(carry, col):
            mean, var, cnt = carry
            v, ok = col
            cnt1 = jnp.minimum(cnt + 1, w)
            d = v - mean
            mean1 = mean + d / cnt1
            var1 = var + ((v - mean1) * d - var) / cnt1
            return (jnp.where(ok, mean1, mean), jnp.where(ok, var1, var),
                    jnp.where(ok, cnt1, cnt)), None

        (mean, var, count), _ = jax.lax.scan(
            take, (jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
                   jnp.zeros(n, jnp.int32)), (x.T, valid.T))
        xn = (x - mean[:, None]) / jnp.sqrt(var + 1e-6)[:, None]
        first = (jnp.arange(w)[None, :] + (w - count)[:, None]) % w
        return (jnp.take_along_axis(self._bin(xn), first, axis=1), count,
                mean, var)

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

    def _logits(self, params, h):
        with jax.named_scope("lm_head"):
            return self._mm(_rms(h, params["norm"], self.cfg.rms_norm_eps),
                            params["head"])

    def _in_blocks(self, fn, *rows):
        """`fn` over row blocks of `seed_rows`, one after another, so a
        whole bucket's windows never stand in memory at once."""
        n, b = rows[0].shape[0], self.seed_rows
        if n <= b:
            return fn(*rows)
        pad = -n % b
        blocks = [jnp.concatenate([r, jnp.zeros((pad,) + r.shape[1:],
                                                r.dtype)]).reshape(
            (-1, b) + r.shape[1:]) for r in rows]
        out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks))
        return jax.tree.map(
            lambda o: o.reshape((-1,) + o.shape[2:])[:n], out)

    # -- the model's surfaces -------------------------------------------------

    def init_state(self, cap: int) -> dict:
        c = self.cfg
        state = {"mean": jnp.zeros(cap, jnp.float32),
                 "var": jnp.ones(cap, jnp.float32),
                 "count": jnp.zeros(cap, jnp.int32),
                 "pos": jnp.zeros(cap, jnp.int32),
                 "hn": jnp.zeros((cap, c.hidden_size), c.compute_dtype)}
        for l in range(self.layers):
            state[f"ctx{l}"] = jnp.zeros(
                (cap, c.context_positions, c.entry_width), c.compute_dtype)
        return state

    def step_score(self, params: dict, rows: dict, v: jax.Array,
                   live: jax.Array):
        """One event a row: the score of the bin that arrived, then the
        row's next state. For a window leaf the new row is the ONE entry
        to append at `rows["pos"]`. Also the step's numbers, in
        `step_stats`' order (`live` masks the padding out of them)."""
        c = self.cfg
        mean, var, cnt, pos = (rows["mean"], rows["var"], rows["count"],
                               rows["pos"])
        token = self._bin((v - mean) / jnp.sqrt(var + 1e-6))
        with jax.named_scope("lm_head"):
            logits = self._mm(rows["hn"], params["head"])
            surprisal = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, token[:, None], axis=1)[:, 0]
        score = jnp.clip(jnp.where(cnt >= self._gate, surprisal, 0.0),
                         0.0, c.score_clip)
        cnt1 = jnp.minimum(cnt + 1, c.window)
        delta = v - mean
        mean1 = mean + delta / cnt1
        var1 = var + ((v - mean1) * delta - var) / cnt1
        out = {"mean": mean1, "var": var1, "count": cnt1, "pos": pos + 1}
        x = params["embed"][token].astype(jnp.float32)
        held = busiest = one_tile = jnp.zeros((), jnp.int32)
        for l in range(self.layers):
            x, entry, counts = self._block_decode(
                params[f"layer{l}"], x, rows[f"ctx{l}"], pos, live)
            out[f"ctx{l}"] = entry
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
            one_tile.astype(jnp.float32)])
        return score, out, stats

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """State of `n` devices after their stored windows (`[n, W]`
        chronological left-padded): the prefill form over each window."""
        c = self.cfg
        n, w = x.shape
        tokens, count, mean, var = self._window_tokens(x, valid)
        h, entries = self._prefill(params, tokens, count)
        last = h[jnp.arange(n), jnp.maximum(count - 1, 0)]
        state = self.init_state(n)
        state.update(mean=mean, var=jnp.maximum(var, 1e-6),
                     count=jnp.minimum(count, c.window), pos=count)
        state["hn"] = jnp.where(
            (count > 0)[:, None],
            _rms(last, params["norm"], c.rms_norm_eps), 0.0).astype(
                c.compute_dtype)
        for l, entry in enumerate(entries):
            state[f"ctx{l}"] = state[f"ctx{l}"].at[:, :w].set(entry)
        return state

    def score(self, params: dict, x: jax.Array, valid: jax.Array) -> jax.Array:
        """The newest value's score from a stored window alone (the query
        path): the surprisal of its bin under the positions before it."""
        c = self.cfg

        def rows(x, valid):
            n = x.shape[0]
            tokens, count, _, _ = self._window_tokens(x, valid)
            h, _ = self._prefill(params, tokens, count)
            at = jnp.maximum(count - 1, 1)
            logits = self._logits(params, h[jnp.arange(n), at - 1])
            surprisal = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, tokens[jnp.arange(n), at][:, None], axis=1)[:, 0]
            return jnp.clip(jnp.where(count >= self._gate, surprisal, 0.0),
                            0.0, c.score_clip)

        return self._in_blocks(rows, x, valid)

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


def runs_one_tile(counts, tile=EXPERT_TILE):
    """Of the held experts' runs `counts` `[held]`, those that
    `Dsv3StreamModel.routed`'s straight-line pass serves whole (an
    empty run too); the others enter its overflow loop."""
    return (counts <= tile).sum()


def _precision(cdt):
    return (jax.lax.Precision.HIGHEST if jnp.dtype(cdt) == jnp.float32
            else None)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
