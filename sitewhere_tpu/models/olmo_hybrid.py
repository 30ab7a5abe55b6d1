"""`olmo-hybrid-stream`: an Olmo-Hybrid-7B block stack as a streaming
anomaly scorer (tokens, score and gate as models/seqblocks.py has them).

The block is the published one (config.json of allenai/Olmo-Hybrid-7B;
the configuration's keys keep their published names, so a catalog row
can be handed over as it is). Layer `l` is of one of two kinds
(`layer_types[l]`), and both take the residual stream `x` as it is: the
norms follow what they norm (`x <- x + RMSNorm(mixer(x))`, then `x <- x
+ RMSNorm(MLP(x))`, a SiLU-gated MLP), as in the Olmo-2 and -3 family.

`linear_attention`, the gated delta rule, `H = linear_num_value_heads`
heads with a matrix state `S` `[dk, dv]` each (`linear_key_head_dim`,
`linear_value_head_dim`), one event `t` of a device:

    z = [x Wq | x Wk | x Wv]                   H*dk + H*dk + H*dv channels
    y = SiLU(sum_j conv[j] * z_{t-K+1+j})      causal, depthwise, K taps
    q, k <- y's heads, q / ||q|| * dk^-1/2, k / ||k||;  v <- y's last H*dv
    beta = (2 if linear_allow_neg_eigval else 1) * sigmoid(x Wb)     [H]
    alpha = exp(-exp(A_log) * softplus(x Wa + dt_bias))              [H]
    S <- alpha S;  r = v - S^T k;  S <- S + k (beta r)^T;  o = S^T q
    mixer(x) = concat_h(RMSNorm_dv(o_h) * SiLU(x Wg)_h) Wo

`||.||` is `sqrt(sum of squares + 1e-6)`. The conv's inputs `z` are
rounded to the type its taps rest in before any tap is read, this
event's too, so an input is the same number at each of the `K` events
that read it.

`full_attention`: `q, k, v = x Wq, x Wk, x Wv`, an RMSNorm over the
whole of `q` and of `k`, `num_attention_heads` heads of `hidden_size /
num_attention_heads` with a key-value head each, `softmax(q K^T /
sqrt(d)) V` over every position `j <= t`, `Wo`; no bias, and no rotary
turn (`rope_parameters.rope_theta` is null).

What the config leaves open is set by the convention of its key names
(the benchmark's configuration lists each under `assumed`).

Weights in `compute_dtype`, matrix products in it with float32
accumulation; the recurrence (`alpha`, `beta`, `S^T k`, the outer
product, `S^T q`) in float32 on float32 operands; norms, softmax, gate,
residual stream and score in float32.

State leaves (scoring/stream.py, "Contract with the model"): `mean`,
`var` f32, `count`, `pos` i32 `[rows]`; `hn` `[rows, hidden]`; a linear
layer's `s<l>` `[rows, H / g, dk, g * dv]` float32, `g` heads side by
side in a row of lanes (`g` the fewest whose `g * dv` values are whole
lane tiles: two heads of 192 are three tiles, nothing padded at rest,
where `[.., dv]` would rest 192 as 256) and `c<l>` `[rows, (K - 1) *
channels / 128, 128]`, the conv's last `K - 1` inputs, oldest first; a
full layer's `k<l>`, `v<l>` `[rows, context_positions, hidden]`, the
only window leaves, bounded. `S` RESTS in float32: it is a sum over a
device's whole life, and rounding it at every event would compound
where a context entry is rounded once. A step reads a row's `S` whole
and returns it whole. A full row is seeded again from its last `window`
values, recurrent state included: that forgets what `S` held of older
events.

Two forms of the same numbers. The decode form, one event a row, takes
the state as it rests: `S` is never reshaped, a head's scalars and its
key and query are laid over the head's lanes instead. The prefill
form (seeding, the query path) runs every product over the window's
positions at once and the recurrence as a `lax.scan` of the decode
form's own cell; `_window_tokens` hands it windows with the valid
values first, so a position at or past a row's `count` leaves state and
taps as they were.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from sitewhere_tpu.models.seqblocks import SEED_TOKENS, SeqBlocks, rms
from sitewhere_tpu.ops import state_kernel

_LAYERS = 32              # the published depth: periods of four
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


@dataclass(frozen=True)
class OlmoHybridConfig:
    # the published config.json's keys, defaults as published
    model_type: str = "olmo_hybrid"
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = _LAYERS
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    layer_types: list = field(
        default_factory=lambda: ([LINEAR] * 3 + [FULL]) * (_LAYERS // 4))
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": None})
    # the streaming scorer round the model
    window: int = 96              # stored values a row is seeded from
    context_positions: int = 384  # positions a full layer's context holds
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0

    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def value_width(self) -> int:
        """A linear layer's values, and a row of its state: `H * dv`."""
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_width + self.value_width


class OlmoHybridStreamModel(SeqBlocks):
    """Functional, like every model here: the instance holds the
    configuration, weights are passed in."""

    name = "olmo-hybrid-stream"
    streaming = True
    # the numbers `step_score` returns beside the scores, by the names
    # the session feeds the metrics registry under (`scoring.<name>`)
    step_stats = ("ctx.positions", "state.decay", "state.absmax",
                  "state.in_place", "ctx.at_rest", "ctx.read_positions")
    stat_families = (SeqBlocks.context_stats, SeqBlocks.state_stats)

    def __init__(self, cfg: OlmoHybridConfig = OlmoHybridConfig()):
        n = cfg.num_hidden_layers
        for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("num_key_value_heads", cfg.num_attention_heads),
                          ("linear_num_key_heads",
                           cfg.linear_num_value_heads)):
            if getattr(cfg, key) != want:
                raise ValueError(f"olmo-hybrid-stream computes {key}="
                                 f"{want!r} only, not {getattr(cfg, key)!r}")
        if (cfg.rope_parameters or {}).get("rope_theta") is not None:
            raise ValueError("olmo-hybrid-stream turns no position: "
                             "rope_parameters.rope_theta must be null")
        if len(cfg.layer_types) < n:
            raise ValueError(f"layer_types names fewer than {n} layers")
        self.kinds = list(cfg.layer_types[:n])
        if set(self.kinds) - {LINEAR, FULL}:
            raise ValueError("olmo-hybrid-stream: a kind of layer it "
                             "cannot compute")
        if not cfg.window <= cfg.context_positions:
            raise ValueError("a context holds fewer positions than the "
                             "window it is seeded from")
        for what, width in (("a position's keys", cfg.hidden_size),
                            ("the conv's channels", cfg.conv_channels)):
            if width % 128:
                raise ValueError(f"{what} are no whole lane tiles")
        if cfg.hidden_size % cfg.num_attention_heads:
            raise ValueError("hidden_size is no whole number of heads")
        # heads that share a row of the state's lanes: the fewest whose
        # values are whole lane tiles side by side (two of 192)
        self._group = 128 // math.gcd(cfg.linear_value_head_dim, 128)
        if cfg.linear_num_value_heads % self._group:
            raise ValueError("the state's heads are no whole lane tiles")
        self.cfg = cfg
        self.layers = n
        # a row of a linear layer's two leaves (`init_state`)
        self._state_shape = (
            cfg.linear_num_value_heads // self._group,
            cfg.linear_key_head_dim, self._group * cfg.linear_value_head_dim)
        self._taps_shape = ((cfg.linear_conv_kernel_dim - 1)
                            * cfg.conv_channels // 128, 128)
        # bytes of a layer's state a row, which the kernel reads and
        # writes whole (`SeqBlocks.state_stats`)
        self.state_row_bytes = 4 * math.prod(self._state_shape)
        # state leaves that are windows -> the leaf that holds the
        # position a step appends at (scoring/stream.py); the linear
        # layers' leaves are rows, rewritten whole
        self.windows = {f"{kv}{l}": "pos" for l in range(n)
                        if self.kinds[l] == FULL for kv in "kv"}
        # ...each read where it rests, in its layer's turn
        self.at_rest = tuple(self.windows)
        # rows one seeding call takes (StreamingRing.load blocks by it)
        self.seed_rows = max(1, SEED_TOKENS // cfg.window)
        self._gate = max(8, cfg.window // 8)
        self._scale = cfg.head_dim ** -0.5

    # -- weights ------------------------------------------------------------

    def _block_shapes(self, layer: int) -> dict:
        c = self.cfg
        h, w, f = c.hidden_size, c.compute_dtype, jnp.float32
        block = {"mixer_norm": ((h,), f), "mlp_norm": ((h,), f),
                 "mlp": {"gate": ((h, c.intermediate_size), w),
                         "up": ((h, c.intermediate_size), w),
                         "down": ((c.intermediate_size, h), w)}}
        if self.kinds[layer] == FULL:
            block.update({"q": ((h, h), w), "k": ((h, h), w),
                          "v": ((h, h), w), "o": ((h, h), w),
                          "q_norm": ((h,), f), "k_norm": ((h,), f)})
            return block
        heads = c.linear_num_value_heads
        block.update({
            "q": ((h, c.key_width), w), "k": ((h, c.key_width), w),
            "v": ((h, c.value_width), w), "g": ((h, c.value_width), w),
            "o": ((c.value_width, h), w), "a": ((h, heads), w),
            "b": ((h, heads), w),
            "conv": ((c.linear_conv_kernel_dim, c.conv_channels), w),
            "A_log": ((heads,), f), "dt_bias": ((heads,), f),
            "o_norm": ((c.linear_value_head_dim,), f)})
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

    def init(self, rng: jax.Array) -> dict:
        """`SeqBlocks.init`'s weights, and a linear layer's two vectors
        as the family draws them: `A` uniform in (0, 16), the step `dt`
        log-uniform in (0.001, 0.1), `dt_bias` its inverse softplus."""
        params = super().init(rng)
        heads = self.cfg.linear_num_value_heads
        for l in range(self.layers):
            if self.kinds[l] != LINEAR:
                continue
            ka, kd = jax.random.split(jax.random.fold_in(rng, 1 << 20 | l))
            dt = jnp.exp(jax.random.uniform(
                kd, (heads,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
            params[f"layer{l}"].update(
                A_log=jnp.log(jax.random.uniform(
                    ka, (heads,), jnp.float32, 1e-3, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)))
        return params

    # -- the linear layer -----------------------------------------------------

    def _gdn_project(self, p, x):
        """What a linear layer takes of tokens `x` `[..., hidden]`: the
        conv's inputs `[..., channels]` as they rest, the output gate's
        input `[..., H * dv]`, `alpha` and `beta` `[..., H]`."""
        c = self.cfg
        with jax.named_scope("gdn_project"):
            z = jnp.concatenate([self._mm(x, p["q"]), self._mm(x, p["k"]),
                                 self._mm(x, p["v"])], -1).astype(
                                     c.compute_dtype)
            alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
                self._mm(x, p["a"]) + p["dt_bias"]))
            beta = jax.nn.sigmoid(self._mm(x, p["b"])) * (
                2.0 if c.linear_allow_neg_eigval else 1.0)
            return z, self._mm(x, p["g"]), alpha, beta

    def _gdn_conv(self, p, taps, z):
        """The conv over a row's taps `[B, (K - 1) * channels]` and this
        position's input `z` `[B, channels]`. -> (`q`, `k` `[B, H, dk]`
        normed, `v` `[B, H * dv]`, the next taps)."""
        c = self.cfg
        b = z.shape[0]
        heads, dk = c.linear_num_value_heads, c.linear_key_head_dim
        with jax.named_scope("gdn_conv"):
            taps = jnp.concatenate([taps, z], -1)
            wide = taps.astype(jnp.float32)
            y = jax.nn.silu(sum(
                wide[:, j * c.conv_channels:(j + 1) * c.conv_channels]
                * p["conv"][j].astype(jnp.float32)
                for j in range(c.linear_conv_kernel_dim)))
            taps = taps[:, c.conv_channels:]

            def unit(x):
                x = x.reshape(b, heads, dk)
                return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True)
                                         + L2_EPS)

            return (unit(y[:, :c.key_width]) * dk ** -0.5,
                    unit(y[:, c.key_width:2 * c.key_width]),
                    y[:, 2 * c.key_width:], taps)

    def _lanes(self, x):
        """`[B, H, ...]` -> `[B, H / group, ..., group * dv]`: a head's
        numbers over the head's own lanes (a select by lane, which fuses
        into whatever reads it)."""
        group, dv = self._group, self.cfg.linear_value_head_dim
        lane_head = jnp.arange(group * dv) // dv
        x = x.reshape((x.shape[0], x.shape[1] // group, group) + x.shape[2:])
        out = x[:, :, 0, ..., None]
        for j in range(1, group):
            out = jnp.where(lane_head >= j, x[:, :, j, ..., None], out)
        return out

    def _gdn_cell(self, p, s, taps, z, alpha, beta):
        """One position a row: the conv over the row's taps and this
        position's input `z` `[B, channels]`, then the delta rule on the
        state `s` `[B, H / g, dk, g * dv]` as it rests; `taps` `[B, (K -
        1) * channels]`. -> (`o` `[B, H * dv]`, the next state, the next
        taps, the largest magnitude a row's state held `[B]`)."""
        c = self.cfg
        b = z.shape[0]
        q, k, v, taps = self._gdn_conv(p, taps, z)
        with jax.named_scope("gdn_state"):
            lanes = self._lanes
            kw, decay = lanes(k), lanes(alpha)
            v = v.reshape(b, -1, self._state_shape[-1])
            # S <- alpha S; r = v - S^T k; S <- S + k (beta r)^T; o = S^T q.
            # ONE pass over S as it was found gives S^T k, S^T q and its
            # largest magnitude (three reductions of one read), a second
            # writes the next S: o = alpha S^T q + (k . q) beta r
            sk, sq, largest = jax.lax.reduce(
                (s * kw, s * lanes(q), jnp.abs(s)),
                (jnp.float32(0), jnp.float32(0), jnp.float32(0)),
                lambda a, b: (a[0] + b[0], a[1] + b[1],
                              jnp.maximum(a[2], b[2])), (2,))
            write = lanes(beta) * (v - decay * sk)
            o = decay * sq + lanes((k * q).sum(-1)) * write
            s = decay[:, :, None, :] * s + kw * write[:, :, None, :]
        return (o.reshape(b, c.value_width), s, taps,
                largest.reshape(b, -1).max(1))

    def _gdn_rows(self, p, table, dev, taps, z, alpha, beta):
        """`_gdn_cell` on rows `dev` of a layer's state `table`, each
        read out of the row it rests in and written back into it by one
        kernel (ops/state_kernel.py): the same lines, the state never
        gathered. -> (the table, `o`, the next taps, the largest
        magnitude a row's state held, 0 for padding, and how many live
        rows were updated where they rested)."""
        b, groups = z.shape[0], self._state_shape[0]
        q, k, v, taps = self._gdn_conv(p, taps, z)
        with jax.named_scope("gdn_state"):
            # what a row brings beside its state: keys and queries a head
            # a lane, four vectors over the lanes; nothing of `S`'s shape
            keys = jnp.stack([k, q], 1).swapaxes(2, 3)
            vec = jnp.stack([v.reshape(b, groups, -1), self._lanes(alpha),
                             self._lanes(beta),
                             self._lanes((k * q).sum(-1))], 1)
            table, o, held = state_kernel.update_rows(table, dev, keys, vec)
        return (table, o.reshape(b, -1), taps, held,
                (dev < table.shape[0] - 1).sum(dtype=jnp.int32))

    def _gdn_out(self, p, x, o, gate):
        """Heads `o` `[..., H * dv]` normed one by one, gated, through
        `Wo`, and the layer's norm on what comes out: `x`'s next value."""
        c = self.cfg
        with jax.named_scope("gdn_out"):
            heads = o.shape[:-1] + (c.linear_num_value_heads,
                                    c.linear_value_head_dim)
            y = rms(o.reshape(heads), p["o_norm"], c.rms_norm_eps) \
                * jax.nn.silu(gate.reshape(heads))
            return x + rms(self._mm(y.reshape(o.shape), p["o"]),
                           p["mixer_norm"], c.rms_norm_eps)

    def _linear_decode(self, p, x, state, taps):
        """A linear layer on `x` `[B, hidden]`, one event a row; `state`
        and `taps` are the layer's two leaves in turn
        (scoring/stream.py, `RowsInTurn`). The state is updated where it
        rests on a TPU, where its rows fit the kernel's VMEM
        (ops/state_kernel.py `fits`); elsewhere its rows are read,
        stepped by `_gdn_cell` and written whole: one algorithm, and the
        plain path is the kernel's twin in the tests. -> (x, `alpha`
        `[B, H]`, the largest magnitude a row's `s` held `[B]`, the rows
        updated where they rested)."""
        z, gate, alpha, beta = self._gdn_project(p, x)
        tapped = taps.read(x).reshape(x.shape[0], -1)

        def plain(table, dev, taps, z, alpha, beta):
            # the ring's own gather and scatter, as a function of the
            # table: a turn of `state`'s kind over the table handed in
            rows = type(state)(table, dev)
            o, s, taps, held = self._gdn_cell(p, rows.read(z), taps, z,
                                              alpha, beta)
            o = rows.write(s, o)
            return rows.table, o, taps, held, jnp.int32(0)

        def turn(table, dev):
            args = (table, dev, tapped, z, alpha, beta)
            if not state_kernel.fits(table.shape, table.dtype):
                return plain(*args)
            return jax.lax.platform_dependent(
                *args, default=plain,
                tpu=functools.partial(self._gdn_rows, p))

        o, c1, held, in_place = state.update(turn, x)
        x = taps.write(c1.reshape((-1,) + self._taps_shape),
                       self._gdn_out(p, x, o, gate))
        return x, alpha, held, in_place

    def _linear_prefill(self, p, x, count):
        """Over `[n, S, hidden]`: the cell scanned over the positions, a
        row's state and taps held where they are from its `count` on.
        -> (x, the state after position `count - 1`, the taps then)."""
        c = self.cfg
        n, s_len, _ = x.shape
        z, gate, alpha, beta = self._gdn_project(p, x)

        def position(carry, at):
            s, taps = carry
            z_t, alpha_t, beta_t, t = at
            o, s1, taps1, _ = self._gdn_cell(p, s, taps, z_t, alpha_t,
                                             beta_t)
            live = t < count
            return (jnp.where(live[:, None, None, None], s1, s),
                    jnp.where(live[:, None], taps1, taps)), o

        start = (jnp.zeros((n,) + self._state_shape, jnp.float32),
                 jnp.zeros((n, math.prod(self._taps_shape)),
                           c.compute_dtype))
        (s, taps), o = jax.lax.scan(
            position, start, (z.swapaxes(0, 1), alpha.swapaxes(0, 1),
                              beta.swapaxes(0, 1), jnp.arange(s_len)))
        return self._gdn_out(p, x, o.swapaxes(0, 1), gate), s, taps

    # -- the full layer ---------------------------------------------------------

    def _full(self, p, x, attend):
        """A full layer's mixer on `x` `[..., hidden]`; `attend(q, k, v)`
        is the form. -> (x, the stored keys, the stored values)."""
        c = self.cfg
        cdt = c.compute_dtype
        with jax.named_scope("attn_full"):
            q = rms(self._mm(x, p["q"]), p["q_norm"], c.rms_norm_eps)
            k = rms(self._mm(x, p["k"]), p["k_norm"], c.rms_norm_eps).astype(
                cdt)
            v = self._mm(x, p["v"]).astype(cdt)
            a = attend(q.reshape(q.shape[:-1] + (c.num_attention_heads,
                                                 c.head_dim)), k, v)
            return x + rms(self._mm(a.reshape(x.shape), p["o"]),
                           p["mixer_norm"], c.rms_norm_eps), k, v

    def _mlp_half(self, p, x):
        with jax.named_scope("dense_mlp"):
            return x + rms(self._mlp(p["mlp"], x), p["mlp_norm"],
                           self.cfg.rms_norm_eps)

    def _prefill(self, params, tokens, count):
        """Every block over `[n, S]` tokens: (hidden states before the
        final norm `[n, S, hidden]`, what a layer leaves a row: a linear
        layer its state and taps after position `count - 1`, a full one
        its keys and values `[n, S, hidden]`)."""
        c = self.cfg
        x = params["embed"][tokens].astype(jnp.float32)
        left = []
        for l in range(self.layers):
            p = params[f"layer{l}"]
            if self.kinds[l] == LINEAR:
                x, *rest = self._linear_prefill(p, x, count)
            else:
                x, *rest = self._full(
                    p, x, lambda q, k, v: self._causal_prefill(
                        q, k, v, count, c.num_key_value_heads))
            left.append(rest)
            x = self._mlp_half(p, x)
        return x, left

    # -- the model's surfaces -------------------------------------------------

    def _leaves(self, layer: int) -> tuple:
        """The names of the two leaves a layer keeps a row."""
        return ((f"s{layer}", f"c{layer}") if self.kinds[layer] == LINEAR
                else (f"k{layer}", f"v{layer}"))

    def init_state(self, cap: int) -> dict:
        c = self.cfg
        state = self._row_state(cap)
        for l in range(self.layers):
            first, second = self._leaves(l)
            if self.kinds[l] == LINEAR:
                state[first] = jnp.zeros((cap,) + self._state_shape,
                                         jnp.float32)
                state[second] = jnp.zeros((cap,) + self._taps_shape,
                                          c.compute_dtype)
            else:
                for name in (first, second):
                    state[name] = jnp.zeros(
                        (cap, c.context_positions, c.hidden_size),
                        c.compute_dtype)
        return state

    def step_score(self, params: dict, rows: dict, v: jax.Array,
                   live: jax.Array):
        """One event a row: the score of the bin that arrived, then the
        row's next state. A linear layer's `s` and `c` come in turn
        (scoring/stream.py, `RowsInTurn`): read when the layer starts,
        written whole before the next one starts; a full layer's window
        leaves as `ContextAtRest`s: the layer appends its ONE entry a
        row and reads the table behind it (`_decode_at_rest`); nothing
        is returned for either. Also the step's numbers, in `step_stats`'
        order (`live` masks the padding out of them): the mean position,
        the mean of `alpha` over live rows, heads and linear layers, and
        the largest `|S|` found in the live rows' states, which is what
        their last events left there."""
        c = self.cfg
        pos = rows["pos"]
        token, score, out = self._arrive(params, rows, v)
        x = params["embed"][token].astype(jnp.float32)
        decay, largest = jnp.float32(0), jnp.float32(0)
        in_place = at_rest = read = jnp.int32(0)
        for l in range(self.layers):
            p = params[f"layer{l}"]
            first, second = self._leaves(l)
            if self.kinds[l] == LINEAR:
                # the layer's rows when it starts, back in the table
                # before the next one starts: the step holds one layer's
                # rows at a time
                x, alpha, held, rested = self._linear_decode(
                    p, x, rows[first], rows[second])
                decay += jnp.where(live[:, None], alpha, 0).sum()
                largest = jnp.maximum(largest,
                                      jnp.where(live, held, 0).max())
                in_place += rested
            else:
                x, _, _ = self._full(
                    p, x, lambda q, k, v, kctx=rows[first],
                    vctx=rows[second]: self._decode_at_rest(
                        q, k, v, kctx, vctx, pos, c.num_key_value_heads))
                at_rest += rows[first].read_rows
                read += rows[first].read_positions
            x = self._mlp_half(p, x)
        out["hn"] = rms(x, params["norm"], c.rms_norm_eps).astype(
            c.compute_dtype)
        n_live = jnp.maximum(live.sum(), 1)
        stats = jnp.stack([
            jnp.where(live, pos, 0).sum() / n_live,
            decay / (n_live * max(self.kinds.count(LINEAR), 1)
                     * c.linear_num_value_heads),
            largest, in_place.astype(jnp.float32),
            at_rest.astype(jnp.float32), read.astype(jnp.float32)])
        return score, out, stats

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """State of `n` devices after their stored windows (`[n, W]`
        chronological left-padded): the prefill form over each window."""
        if state_kernel.fits((1,) + self._state_shape, jnp.float32):
            # traced once, when seeding starts: the step's kernel will want
            # its library, which comes in while the seeding calls wait on
            # the chip (begun where the model is built, it took the fleet's
            # registration 1.3 s longer: PERF.md section 6, PR 36)
            state_kernel.import_ahead()
        state, left, _ = self._warm(params, x, valid)
        w = x.shape[1]
        for l, (first, second) in enumerate(left):
            a, b = self._leaves(l)
            if self.kinds[l] == LINEAR:
                state[a], state[b] = first, second.reshape(state[b].shape)
            else:
                state[a] = state[a].at[:, :w].set(first)
                state[b] = state[b].at[:, :w].set(second)
        return state
