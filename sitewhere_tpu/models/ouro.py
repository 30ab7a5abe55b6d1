"""`ouro-stream`: an Ouro-2.6B block stack as a streaming anomaly scorer
(tokens, score and gate as models/seqblocks.py has them).

The block is the published one (config.json of ByteDance/Ouro-2.6B,
`model_type` `ouro`, a looped language model; the configuration's keys
keep their published names, so a catalog row can be handed over as it
is). One token `x` at position `t`:

    h_0 = embed(token)
    for pass r = 1 .. U (U = total_ut_steps):
        y = h_{r-1}
        for layer l = 0 .. L-1 (the SAME weights in every pass):
            y = y + RMSNorm_a2(attn_{l,r}(RMSNorm_a1(y)))
            y = y + RMSNorm_m2(W_down(silu(n W_gate) * (n W_up))),
                n = RMSNorm_m1(y)
        h_r = RMSNorm_final(y)
    logits = h_U W_head                               (an untied head)

`attn_{l,r}(u)`: `q = u Wq`, `k = u Wk`, `v = u Wv`, `num_attention_heads`
and `num_key_value_heads` heads of `head_dim`, no bias; `q` and `k` get
the rotary turn at `t` over all `head_dim` dimensions, pairs `(i, i +
d / 2)`, theta `rope_theta`; `softmax(q K_{l,r}^T / sqrt(d)) V_{l,r}`
over every position `j <= t`, query head `h` on key-value head `h //
(heads / kv)`; `Wo`. `K_{l,r}`, `V_{l,r}` are the keys and values layer
`l` made IN PASS `r` at every position: one context for each (pass,
layer), `U x L` of them. With `early_exit_threshold` 1 every token runs
every pass and the exit gate touches no logit: the gate is not held, and
a threshold under 1 is refused.

The passes are a `lax.scan` carrying the residual stream (the final
norm, `loop_norm`, closes each pass and feeds the next), the stage's
layers a `lax.scan` over weights stacked `[layers, ...]` (`loop_pass`):
one compiled body for `U x L` layer runs, whose weights are streamed
once a pass.

Weights in `compute_dtype` (norms too: the published checkpoint's
type), matrix products in it with float32 accumulation; norms, softmax,
residual stream and score in float32.

State leaves (scoring/stream.py, "Contract with the model"): `mean`,
`var` f32, `count`, `pos` i32 `[rows]`; `hn` `[rows, hidden]`; `k` and
`v` `[rows, context_positions, U x L x kv_width]`, the only window
leaves, bounded: a row's `U x L` contexts side by side, context `s = r x
L + l` (pass `r` from 0) in lanes `[s x kv_width, (s + 1) x kv_width)`.
A slot reads only its own lanes: on a TPU through `ops/context_kernel.py`,
whose index map takes the block as one more prefetched scalar (`at_rest`),
the position's own entry handed beside the table; elsewhere the plain
twin, `_decode_rows` over the gathered block. A step keeps its entries in
a line of every slot a row and appends the two lines at the row's
position once the last pass has ended: one append an entry, each a loop
of row updates in XLA, was 6.6 of an 18.3 ms step on a v5e (PERF.md
section 6, PR 41). Rope is applied before an entry is stored.

Two forms of the same numbers: the decode form, one event a row, and
the prefill form (seeding, the query path), a masked softmax over the
window through the same loop; `_window_tokens` hands it windows with the
valid values first, so a position at or past a row's `count` leaves no
trace in what a row keeps.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

from sitewhere_tpu.models import seqblocks
from sitewhere_tpu.models.seqblocks import SEED_TOKENS, SeqBlocks, rms
from sitewhere_tpu.ops import context_kernel

_LAYERS = 48              # the published depth
FULL = "full_attention"
SEED_BYTES = 3 << 28      # rows of the context tables one seeding call
                          # makes: 768 MiB, beside a ring of 11.45 GB


def turn_heads(x, cos, sin, d: int):
    """`seqblocks.rope_halves` of heads of `d` laid side by side in the
    last axis of `x`, pairs `(i, i + d / 2)` of each head, the same
    numbers to the bit; `cos`, `sin` `[..., d / 2]` broadcast against
    `x[..., :d / 2]`. Nothing is reshaped into heads: at a v5e's tiled
    layouts that reshape is no view, and its compiler transposed the
    stacked projections of every layer to make it one (two copies of
    100 MB a step; PERF.md section 6, PR 41). A lane's partner lies `d /
    2` lanes on, or back, in its own head: a rotation of the lanes."""
    half, heads = d // 2, x.shape[-1] // d
    lane = jnp.arange(x.shape[-1]) % d < half
    partner = jnp.where(lane, -jnp.roll(x, -half, axis=-1),
                        jnp.roll(x, half, axis=-1))
    return (x * jnp.tile(jnp.concatenate([cos, cos], -1), heads)
            + partner * jnp.tile(jnp.concatenate([sin, sin], -1), heads))


@dataclass(frozen=True)
class OuroConfig:
    # the published config.json's keys, defaults as published
    model_type: str = "ouro"
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = _LAYERS
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    max_window_layers: int = _LAYERS
    rms_norm_eps: float = 1e-6
    rope_scaling: Optional[dict] = None
    rope_theta: float = 1000000
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4
    early_exit_threshold: float = 1
    layer_types: list = field(default_factory=lambda: [FULL] * _LAYERS)
    # the streaming scorer round the model
    window: int = 64              # stored values a row is seeded from
    context_positions: int = 448  # positions each of a row's contexts holds
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0

    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def kv_width(self) -> int:
        """A position's keys (or values) of one context as stored."""
        return self.num_key_value_heads * self.head_dim


class OuroStreamModel(SeqBlocks):
    """Functional, like every model here: the instance holds the
    configuration and tables made from it, weights are passed in."""

    name = "ouro-stream"
    streaming = True
    # the numbers `step_score` returns beside the scores, by the names
    # the session feeds the metrics registry under (`scoring.<name>`)
    step_stats = ("ctx.positions", "ctx.at_rest", "loop.weight_bytes",
                  "ctx.attended_bytes", "ctx.read_positions")
    stat_families = (SeqBlocks.context_stats, SeqBlocks.loop_stats)

    def __init__(self, cfg: OuroConfig = OuroConfig()):
        n = cfg.num_hidden_layers
        for key, want in (("tie_word_embeddings", False),
                          ("use_sliding_window", False),
                          ("rope_scaling", None), ("hidden_act", "silu")):
            if getattr(cfg, key) != want:
                raise ValueError(f"ouro-stream computes {key}={want!r} "
                                 f"only, not {getattr(cfg, key)!r}")
        if cfg.early_exit_threshold < 1:
            raise ValueError("ouro-stream runs every pass for every token: "
                             "an early_exit_threshold under 1 exits early")
        if cfg.total_ut_steps < 1:
            raise ValueError("total_ut_steps: a token runs at least one pass")
        if len(cfg.layer_types) < n:
            raise ValueError(f"layer_types names fewer than {n} layers")
        if set(cfg.layer_types[:n]) - {FULL}:
            raise ValueError("ouro-stream: a kind of layer it cannot compute")
        if cfg.num_attention_heads % cfg.num_key_value_heads \
                or cfg.head_dim % 2:
            raise ValueError("the heads are no whole groups of an even width")
        if cfg.kv_width % 128:
            raise ValueError("a position's keys are no whole lane tiles")
        if not cfg.window <= cfg.context_positions:
            raise ValueError("a context holds fewer positions than the "
                             "window it is seeded from")
        self.cfg = cfg
        self.layers = n
        self.passes = cfg.total_ut_steps
        self.slots = self.passes * n           # contexts a row holds
        self.windows = {"k": "pos", "v": "pos"}
        # ...each handed over where it rests, and read a block at a time
        self.at_rest = ("k", "v")
        # rows one seeding call takes (StreamingRing.load blocks by it):
        # its tokens' activations and its rows of the context tables fit
        row = 2 * cfg.context_positions * self.slots * cfg.kv_width \
            * jnp.dtype(cfg.compute_dtype).itemsize
        self.seed_rows = max(1, min(SEED_TOKENS // cfg.window,
                                    SEED_BYTES // row))
        self._gate = max(8, cfg.window // 8)
        self._scale = cfg.head_dim ** -0.5
        # the turn's tables over every position a context can reach
        self._cos, self._sin = seqblocks.rope_tables(
            cfg.context_positions, cfg.head_dim, cfg.rope_theta)
        # bytes of the stage's layer weights the passes stream a step
        self._loop_bytes = self.passes * sum(
            jnp.dtype(dtype).itemsize * math.prod(shape)
            for shape, dtype in self._layer_shapes().values())

    # -- weights ------------------------------------------------------------

    def _layer_shapes(self) -> dict:
        """The stage's layers, stacked: name -> ([layers, ...], dtype)."""
        c = self.cfg
        h, n, w = c.hidden_size, self.layers, c.compute_dtype
        qo, kv = c.num_attention_heads * c.head_dim, c.kv_width
        return {"attn_norm": ((n, h), w), "q": ((n, h, qo), w),
                "k": ((n, h, kv), w), "v": ((n, h, kv), w),
                "o": ((n, qo, h), w), "attn_out_norm": ((n, h), w),
                "mlp_norm": ((n, h), w),
                "gate": ((n, h, c.intermediate_size), w),
                "up": ((n, h, c.intermediate_size), w),
                "down": ((n, c.intermediate_size, h), w),
                "mlp_out_norm": ((n, h), w)}

    def param_shapes(self) -> dict:
        """The checkpoint's layout: name -> (shape, dtype), nested."""
        c = self.cfg
        return {"embed": ((c.vocab, c.hidden_size), c.compute_dtype),
                "layers": self._layer_shapes(),
                "norm": ((c.hidden_size,), c.compute_dtype),
                "head": ((c.hidden_size, c.vocab), c.compute_dtype)}

    # -- the loop ---------------------------------------------------------------

    def _layer(self, p, y, at, attend):
        """One layer on the residual stream `y` `[..., hidden]` at
        positions `at`, sandwich norms round both halves; `attend(q, k,
        v)` is the form. -> (y, the stored keys, the stored values)."""
        c = self.cfg
        eps, d, cdt = c.rms_norm_eps, c.head_dim, c.compute_dtype
        u = rms(y, p["attn_norm"], eps)
        with jax.named_scope("gqa_project"):
            cos, sin = jnp.asarray(self._cos)[at], jnp.asarray(self._sin)[at]
            q = turn_heads(self._mm(u, p["q"]), cos, sin, d)
            q = q.reshape(q.shape[:-1] + (-1, d))
            k = turn_heads(self._mm(u, p["k"]), cos, sin, d).astype(cdt)
            v = self._mm(u, p["v"]).astype(cdt)
        with jax.named_scope("attn_full"):
            a = attend(q, k, v)
            y = y + rms(self._mm(a.reshape(y.shape[:-1] + (-1,)), p["o"]),
                        p["attn_out_norm"], eps)
        with jax.named_scope("dense_mlp"):
            y = y + rms(self._mlp(p, rms(y, p["mlp_norm"], eps)),
                        p["mlp_out_norm"], eps)
        return y, k, v

    def _loop(self, params, x, layer, carry):
        """The passes: pass `r` runs `layer(p, y, carry, slot) -> (y,
        carry)` over the stage's layers, slot `r x L + l`, and the final
        norm closes it, its output the next pass's input. -> (y after
        the last layer of the last pass, before the final norm; carry).

        A scan over the passes' first slots, whose body tests nothing:
        as a `fori_loop` whose body normed where its index was past 0,
        a v5e's compiled step normed before the first pass too (the
        prefill of one layer run twice read -0.551 where the reference
        reads -1.724, every pass's input normed -0.571; the same lines
        unrolled, or on the CPU, agree to the bit; PERF.md section 6,
        PR 41)."""
        eps = self.cfg.rms_norm_eps

        def one_pass(state, first):
            h, _, carry = state

            def each(state, ps):
                p, slot = ps
                return layer(p, *state, slot), None

            with jax.named_scope("loop_pass"):
                (y, carry), _ = jax.lax.scan(
                    each, (h, carry),
                    (params["layers"], first + jnp.arange(self.layers)))
            with jax.named_scope("loop_norm"):
                h = rms(y, params["norm"], eps)
            return (h, y, carry), None

        x = x.astype(jnp.float32)
        (_, y, carry), _ = jax.lax.scan(
            one_pass, (x, x, carry),
            jnp.arange(self.passes, dtype=jnp.int32) * self.layers)
        return y, carry

    def _into_slot(self, lines, slot, *entries):
        """Keys and values `[..., kv_width]` each into the lanes of
        context `slot` of `lines` `[..., slots x kv_width]` each."""
        width = self.cfg.kv_width
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            line, entry, slot * width, axis=-1)
            for line, entry in zip(lines, entries))

    def _prefill(self, params, tokens, count):
        """The loop over `[n, S]` tokens: (hidden states before the final
        norm `[n, S, hidden]`, the keys and values of every slot `[n, S,
        slots x kv_width]` each)."""
        c = self.cfg
        n, s_len = tokens.shape
        at = jnp.arange(s_len)

        def layer(p, y, seeded, slot):
            y, k, v = self._layer(p, y, at, lambda q, k, v: self._causal_prefill(
                q, k, v, count, c.num_key_value_heads))
            return y, self._into_slot(seeded, slot, k, v)

        empty = jnp.zeros((n, s_len, self.slots * c.kv_width),
                          c.compute_dtype)
        return self._loop(params, params["embed"][tokens], layer,
                          (empty, empty))

    def _attend(self, q, k, v, keys, vals, pos, slot):
        """Attention of one token a row over its context `slot`, a block
        of lanes of the ring's tables `keys`, `vals` (`ContextAtRest`s,
        read and not written here: the step appends a row's entries of
        every slot at once when it ends), the position's own entry `k`,
        `v` `[B, kv_width]` in the place of `pos`. On a TPU, in bfloat16
        and at shapes it takes, ONE kernel reads the block where it rests
        and takes the entry beside it (ops/context_kernel.py); elsewhere
        the rows are gathered and `_decode_rows` reads the block with the
        entry laid in: one algorithm, the plain path the kernel's twin in
        the tests. -> (`[B, heads, d]`, live rows read at rest, positions
        copied of each table for them)."""
        c = self.cfg
        kv, width, dev = c.num_key_value_heads, c.kv_width, keys.dev

        def plain(ktab, vtab, q, k, v):
            return (self._decode_rows(q, k, v, keys.rows(slot, width),
                                      vals.rows(slot, width), pos, kv),
                    jnp.int32(0), jnp.int32(0))

        def rested(ktab, vtab, q, k, v):
            return (context_kernel.context_rows(
                ktab, vtab, dev, pos, q, slot, (k, v), kv=kv,
                scale=self._scale),
                *context_kernel.reads(ktab.shape, dev, pos, own=True))

        args = (keys.table, vals.table, q, k, v)
        if (jnp.dtype(c.compute_dtype) != jnp.bfloat16
                or not context_kernel.fits(keys.table.shape, keys.table.dtype,
                                           q.shape[1], kv, width)):
            return plain(*args)
        return jax.lax.platform_dependent(*args, default=plain, tpu=rested)

    # -- the model's surfaces -------------------------------------------------

    def init_state(self, cap: int) -> dict:
        c = self.cfg
        state = self._row_state(cap)
        for name in self.windows:
            state[name] = jnp.zeros(
                (cap, c.context_positions, self.slots * c.kv_width),
                c.compute_dtype)
        return state

    def step_score(self, params: dict, rows: dict, v: jax.Array,
                   live: jax.Array):
        """One event a row: the score of the bin that arrived, then the
        row's next state. `k` and `v` come as `ContextAtRest`s: each
        (pass, layer) reads the lanes of its own context where they rest
        (`_attend`), its entry kept in a line of the row's every slot,
        and the two lines are appended at the row's position when the
        last pass ends; nothing is returned for them. Also the step's
        numbers, in `step_stats`' order (`live` masks the padding out of
        them)."""
        c = self.cfg
        pos = rows["pos"]
        kctx, vctx = rows["k"], rows["v"]
        at = jnp.minimum(pos, c.context_positions - 1)
        token, score, out = self._arrive(params, rows, v)

        def layer(p, y, carry, slot):
            lines, at_rest, copied = carry
            read = []

            def attend(q, k, v):
                a, *counts = self._attend(q, k, v, kctx, vctx, pos, slot)
                read.append(counts)
                return a

            y, k, v = self._layer(p, y, at, attend)
            (rows_read, positions), = read
            return y, (self._into_slot(lines, slot, k, v),
                       at_rest + rows_read, copied + positions)

        empty = jnp.zeros((pos.shape[0], self.slots * c.kv_width),
                          c.compute_dtype)
        y, ((klines, vlines), at_rest, copied) = self._loop(
            params, params["embed"][token], layer,
            ((empty, empty), jnp.int32(0), jnp.int32(0)))
        kctx.append(klines)
        vctx.append(vlines)
        out["hn"] = rms(y, params["norm"], c.rms_norm_eps).astype(
            c.compute_dtype)
        n_live = live.sum()
        entry = 2 * c.kv_width * jnp.dtype(c.compute_dtype).itemsize
        stats = jnp.stack([
            jnp.where(live, pos, 0).sum() / jnp.maximum(n_live, 1),
            at_rest.astype(jnp.float32),
            jnp.float32(self._loop_bytes),
            jnp.where(live, pos + 1, 0).sum().astype(jnp.float32)
            * (self.slots * entry), copied.astype(jnp.float32)])
        return score, out, stats

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """State of `n` devices after their stored windows (`[n, W]`
        chronological left-padded): the prefill form over each window,
        every slot's context from it."""
        state, seeded, _ = self._warm(params, x, valid)
        w = x.shape[1]
        for name, entries in zip(("k", "v"), seeded):
            state[name] = state[name].at[:, :w].set(entries)
        return state
