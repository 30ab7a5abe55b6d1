"""`nemotron-h-stream`: an NVIDIA-Nemotron-3-Super-120B-A12B block stack
as a streaming anomaly scorer (tokens, score and gate as
models/seqblocks.py has them).

The block is the published one (config.json of
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, `model_type`
`nemotron_h`; the configuration's keys keep their published names, so a
catalog row can be handed over as it is). Layer `l` is ONE mixer or ONE
expert layer, pre-normed, of the kind `hybrid_override_pattern[l]`
names: `x <- x + mixer(RMSNorm(x))`; after the last layer one more
RMSNorm, then the head (its own matrix). On the normed input `u`:

`M`, Mamba-2 (`H = mamba_num_heads` heads of `P = mamba_head_dim`, `I =
H * P`, state `N = ssm_state_size` in `n_groups` groups, conv of `K =
conv_kernel` taps), one event `t`:

    (z, xBC, dt) = split(u W_in)                I, I + 2 n_groups N, H
    xBC = SiLU(sum_j conv[j] * xBC_{t-K+1+j} + conv_bias)   causal,
          depthwise, zeros before the device's first event
    x, B, C = split(xBC)                        H heads of P; n_groups of N
    dt_h = softplus(dt_h + dt_bias_h);  a_h = exp(-dt_h exp(A_log_h))
    S_h <- a_h S_h + (dt_h x_h) B_g^T           S_h [P, N], g = h // (H / G)
    y_h = S_h C_g + D_h x_h
    mixer(u) = RMSNormGated(y * SiLU(z)) W_out  over G groups of I / G

(`G = n_groups`).

`E`, the latent expert layer: `s = sigmoid(u W_r^T)` over
`n_routed_experts` in float32, the `num_experts_per_tok` largest of `s +
b` (`b` the selection bias, in the choice only; `n_group` 1: no group
limit), weights the chosen `s` over their sum times
`routed_scaling_factor`; the tokens go down to the latent width, `x_l =
u W_dl` (`moe_latent_size`), each chosen expert is ungated, `relu(x_l
U_e)^2 V_e` (`moe_intermediate_size`), their weighted sum goes back up
through `W_ul`, and one shared expert of the same ungated form
(`moe_shared_expert_intermediate_size`) is added on `u` at the full
width.

`*`, grouped-query attention: `num_attention_heads` query heads of
`head_dim` on `num_key_value_heads`, `softmax(q K^T / sqrt(head_dim)) V`
over every position `j <= t`, `W_o`; no bias, no norm on `q` or `k`,
and no rotary turn (the family's attention applies none: `rope_theta`
is read as unused).

The share held here (`first_expert`, `n_routed_experts_held`): an expert
layer routes over all experts and computes the pairs that land on its
own (models/seqblocks.py, `Experts`); `vocab_held` rows of the
embedding and the head are held, and the quantiser draws its bins from
them. The multi-token prediction module (`num_nextn_predict_layers`)
has no place in a step that yields one score an event, and is refused.

What the config leaves open is set by the family's convention (the
benchmark's configuration lists each under `assumed`). Weights in
`compute_dtype`, matrix products in it with float32 accumulation; the
conv's inputs are rounded to the type its taps rest in before any tap
reads them, this event's too; router, softmax, norms, the conv's sum,
the recurrence and its state, residual stream and score in float32.

State leaves (scoring/stream.py, "Contract with the model"): `mean`,
`var` f32, `count`, `pos` i32 `[rows]`; `hn` `[rows, hidden]`; an `M`
layer's `s<l>` `[rows, H / g, N, g * P]` float32, `g` heads side by side
in a row of lanes (the fewest whose `g * P` values are whole lane tiles:
two heads of 64; the heads of a row share their group's `B` and `C`),
updated in the layer's turn, and `c<l>` `[rows, (K - 1) * (I + 2
n_groups N) / 128, 128]`, the conv's last `K - 1` inputs, oldest first;
a `*` layer's `k<l>`, `v<l>` `[rows, context_positions, kv * head_dim]`,
the only window leaves, bounded, read where they rest. `S` RESTS in
float32: it is a sum over a device's whole life. A full row is seeded
again from its last `window` values, recurrent state included.

Two forms of the same numbers. The decode form, one event a row, takes
the state as it rests: on a TPU ONE kernel a layer updates each row's
`S` in the table (ops/state_kernel.py, the decay-and-write rule, `B` and
`C` as its keys and queries, `dt x` as its values), elsewhere the rows
are read, stepped by `_ssm_cell` and written whole. The prefill form
(seeding, the query path) runs every product over the window's
positions at once and the recurrence as a `lax.scan` of the decode
form's own cell; `_window_tokens` hands it windows with the valid
values first, so a position at or past a row's `count` leaves state and
taps as they were.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from sitewhere_tpu.models.seqblocks import (
    SEED_TOKENS,
    Experts,
    SeqBlocks,
    rms,
    runs_one_tile,
)
from sitewhere_tpu.ops import state_kernel

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the published pattern: 40 Mamba-2, 40 expert and 8 attention layers
_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclass(frozen=True)
class NemotronHConfig:
    # the published config.json's keys, defaults as published
    attention_bias: bool = False
    chunk_size: int = 128
    conv_kernel: int = 4
    expand: int = 2
    head_dim: int = 128
    hidden_size: int = 4096
    hybrid_override_pattern: str = _PATTERN
    intermediate_size: int = 2688
    layer_norm_epsilon: float = 1e-5
    mamba_head_dim: int = 64
    mamba_hidden_act: str = "silu"
    mamba_num_heads: int = 128
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    mlp_bias: bool = False
    mlp_hidden_act: str = "relu2"
    model_type: str = "nemotron_h"
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    moe_shared_expert_overlap: bool = False
    mtp_hybrid_override_pattern: str = "*E"
    n_group: int = 1
    n_groups: int = 8
    n_routed_experts: int = 512
    n_shared_experts: int = 1
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_experts_per_tok: int = 22
    num_hidden_layers: int = 88
    num_key_value_heads: int = 2
    num_logits_to_keep: int = 1
    num_nextn_predict_layers: int = 1
    partial_rotary_factor: float = 1
    rescale_prenorm_residual: bool = True
    residual_in_fp32: bool = False
    rope_theta: float = 10000
    routed_scaling_factor: float = 5
    sliding_window: Any = None
    ssm_state_size: int = 128
    tie_word_embeddings: bool = False
    time_step_floor: float = 0.0001
    time_step_max: float = 0.1
    time_step_min: float = 0.001
    topk_group: int = 1
    use_bias: bool = False
    use_conv_bias: bool = True
    use_mamba_kernels: bool = True
    vocab_size: int = 131072
    # the share of a layer this chip holds (0: all of it)
    first_expert: int = 0
    n_routed_experts_held: int = 0
    vocab_held: int = 0
    # the streaming scorer round the model
    window: int = 96              # stored values a row is seeded from
    context_positions: int = 512  # positions an attention context holds
    compute_dtype: Any = jnp.bfloat16
    score_clip: float = 50.0

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts_held or self.n_routed_experts

    @property
    def vocab(self) -> int:
        return self.vocab_held or self.vocab_size

    @property
    def rms_norm_eps(self) -> float:
        return self.layer_norm_epsilon

    @property
    def mamba_width(self) -> int:
        """`I`: a Mamba-2 layer's heads side by side, `x`, `z` and `y`."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """`x`, `B` and `C`, which the conv runs over."""
        return self.mamba_width + 2 * self.n_groups * self.ssm_state_size

    @property
    def kv_width(self) -> int:
        """A position's keys (or values) as they are stored."""
        return self.num_key_value_heads * self.head_dim


class NemotronHStreamModel(SeqBlocks):
    """Functional, like every model here: the instance holds the
    configuration, weights are passed in."""

    name = "nemotron-h-stream"
    streaming = True
    # the numbers `step_score` returns beside the scores, by the names
    # the session feeds the metrics registry under (`scoring.<name>`)
    step_stats = ("moe.assignments_held", "moe.assignments",
                  "moe.expert_max_tokens", "ctx.positions",
                  "moe.runs_one_tile", "ctx.at_rest", "ctx.read_positions",
                  "state.decay", "state.absmax", "state.in_place")
    stat_families = (SeqBlocks.expert_stats, SeqBlocks.context_stats,
                     SeqBlocks.state_stats)

    def __init__(self, cfg: NemotronHConfig = NemotronHConfig()):
        n = cfg.num_hidden_layers
        for key, want in (("mamba_hidden_act", "silu"),
                          ("mlp_hidden_act", "relu2"),
                          ("attention_bias", False),
                          ("mamba_proj_bias", False), ("mlp_bias", False),
                          ("use_bias", False), ("use_conv_bias", True),
                          ("tie_word_embeddings", False),
                          ("n_shared_experts", 1), ("n_group", 1),
                          ("norm_topk_prob", True),
                          ("num_nextn_predict_layers", 0)):
            if getattr(cfg, key) != want:
                raise ValueError(f"nemotron-h-stream computes {key}="
                                 f"{want!r} only, not {getattr(cfg, key)!r}")
        if len(cfg.hybrid_override_pattern) < n:
            raise ValueError(f"hybrid_override_pattern names fewer than "
                             f"{n} layers")
        self.kinds = list(cfg.hybrid_override_pattern[:n])
        if set(self.kinds) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError("nemotron-h-stream: a kind of layer it cannot "
                             "compute")
        if cfg.mamba_width != cfg.expand * cfg.hidden_size:
            raise ValueError("mamba_num_heads x mamba_head_dim is not "
                             "expand x hidden_size")
        if cfg.first_expert + cfg.experts_held > cfg.n_routed_experts:
            raise ValueError("held experts reach past n_routed_experts")
        if not cfg.window <= cfg.context_positions:
            raise ValueError("a context holds fewer positions than the "
                             "window it is seeded from")
        # heads that share a row of the state's lanes: the fewest whose
        # values are whole lane tiles side by side (two of 64), and of
        # one group of `B` and `C`
        self._group = 128 // math.gcd(cfg.mamba_head_dim, 128)
        per_group = cfg.mamba_num_heads // cfg.n_groups
        if cfg.mamba_num_heads % cfg.n_groups or per_group % self._group:
            raise ValueError("the state's rows of lanes are no whole groups "
                             "of heads")
        for what, width in (("a position's keys", cfg.kv_width),
                            ("the conv's channels", cfg.conv_channels)):
            if width % 128 or not width:
                raise ValueError(f"{what} are no whole lane tiles")
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("the query heads are no whole groups")
        self.cfg = cfg
        self.layers = n
        self.experts = Experts(
            routed=cfg.n_routed_experts, held=cfg.experts_held,
            first=cfg.first_expert, per_token=cfg.num_experts_per_tok,
            scale=float(cfg.routed_scaling_factor), scoring="sigmoid")
        # a row of a Mamba-2 layer's two leaves (`init_state`)
        self._state_shape = (
            cfg.mamba_num_heads // self._group, cfg.ssm_state_size,
            self._group * cfg.mamba_head_dim)
        self._taps_shape = ((cfg.conv_kernel - 1) * cfg.conv_channels // 128,
                            128)
        # bytes of a layer's state a row, which the kernel reads and
        # writes whole (`SeqBlocks.state_stats`)
        self.state_row_bytes = 4 * math.prod(self._state_shape)
        # state leaves that are windows -> the leaf that holds the
        # position a step appends at (scoring/stream.py); the Mamba-2
        # layers' leaves are rows, rewritten whole
        self.windows = {f"{kv}{l}": "pos" for l in range(n)
                        if self.kinds[l] == ATTENTION for kv in "kv"}
        # ...each read where it rests, in its layer's turn
        self.at_rest = tuple(self.windows)
        # rows one seeding call takes (StreamingRing.load blocks by it)
        self.seed_rows = max(1, SEED_TOKENS // cfg.window)
        self._gate = max(8, cfg.window // 8)
        self._scale = cfg.head_dim ** -0.5
        # one trace and one lowering for all of a program's expert
        # layers, whose shapes are the same (models/dsv3.py)
        self._routed = jax.jit(self.routed)

    # -- weights --------------------------------------------------------------

    def _block_shapes(self, layer: int) -> dict:
        c = self.cfg
        h, w, f = c.hidden_size, c.compute_dtype, jnp.float32

        def ungated(width, inner):
            return {"up": ((width, inner), w), "down": ((inner, width), w)}

        block = {"norm": ((h,), f)}
        kind = self.kinds[layer]
        if kind == MAMBA:
            heads, inner = c.mamba_num_heads, c.mamba_width
            block.update({
                "in": ((h, inner + c.conv_channels + heads), w),
                "conv": ((c.conv_kernel, c.conv_channels), w),
                "conv_bias": ((c.conv_channels,), w),
                "A_log": ((heads,), f), "dt_bias": ((heads,), f),
                "D": ((heads,), f), "ssm_norm": ((inner,), f),
                "out": ((inner, h), w)})
        elif kind == ATTENTION:
            width = c.num_attention_heads * c.head_dim
            block.update({"q": ((h, width), w), "k": ((h, c.kv_width), w),
                          "v": ((h, c.kv_width), w), "o": ((width, h), w)})
        else:
            latent = c.moe_latent_size
            block.update({
                "router": {"w": ((c.n_routed_experts, h), f),
                           "bias": ((c.n_routed_experts,), f)},
                "latent_down": ((h, latent), w),
                "latent_up": ((latent, h), w),
                # a leaf an expert: the step reads each where it rests
                "experts": {f"e{e}": ungated(latent,
                                             c.moe_intermediate_size)
                            for e in range(c.experts_held)},
                "shared": ungated(h, c.moe_shared_expert_intermediate_size)})
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
        """`SeqBlocks.init`'s weights, and a Mamba-2 layer's as the family
        draws them: the conv's taps and bias uniform in `+-K^-1/2`; `A`
        uniform in (1, 16), `A_log = log A`; the step `dt` log-uniform in
        (`time_step_min`, `time_step_max`), floored at
        `time_step_floor`, `dt_bias` its inverse softplus; `D` 1."""
        params = super().init(rng)
        c = self.cfg
        heads, bound = c.mamba_num_heads, c.conv_kernel ** -0.5
        for l in range(self.layers):
            if self.kinds[l] != MAMBA:
                continue
            kc, kb, ka, kd = jax.random.split(
                jax.random.fold_in(rng, 1 << 20 | l), 4)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                kd, (heads,), jnp.float32, math.log(c.time_step_min),
                math.log(c.time_step_max))), c.time_step_floor)
            params[f"layer{l}"].update(
                conv=jax.random.uniform(
                    kc, (c.conv_kernel, c.conv_channels), jnp.float32,
                    -bound, bound).astype(c.compute_dtype),
                conv_bias=jax.random.uniform(
                    kb, (c.conv_channels,), jnp.float32, -bound,
                    bound).astype(c.compute_dtype),
                A_log=jnp.log(jax.random.uniform(
                    ka, (heads,), jnp.float32, 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                D=jnp.ones((heads,), jnp.float32))
        return params

    # -- the Mamba-2 layer ----------------------------------------------------

    def _ssm_project(self, p, u):
        """What a Mamba-2 layer takes of normed tokens `u` `[..., hidden]`:
        the gate `z` `[..., I]`, the conv's inputs `[..., channels]` as
        they rest, `dt` and the decay `a` `[..., H]`."""
        c = self.cfg
        inner = c.mamba_width
        with jax.named_scope("ssm_project"):
            zxd = self._mm(u, p["in"])
            dt = jax.nn.softplus(zxd[..., inner + c.conv_channels:]
                                 + p["dt_bias"])
            return (zxd[..., :inner],
                    zxd[..., inner:inner + c.conv_channels].astype(
                        c.compute_dtype),
                    dt, jnp.exp(-dt * jnp.exp(p["A_log"])))

    def _ssm_conv(self, p, taps, xbc):
        """The conv over a row's taps `[B, (K - 1) * channels]` and this
        position's input `xbc` `[B, channels]`. -> (`x` `[B, I]`, `B`,
        `C` `[B, n_groups, N]`, the next taps)."""
        c = self.cfg
        ch, inner = c.conv_channels, c.mamba_width
        b = xbc.shape[0]
        with jax.named_scope("ssm_conv"):
            taps = jnp.concatenate([taps, xbc], -1)
            wide = taps.astype(jnp.float32)
            y = jax.nn.silu(sum(
                wide[:, j * ch:(j + 1) * ch] * p["conv"][j].astype(jnp.float32)
                for j in range(c.conv_kernel))
                + p["conv_bias"].astype(jnp.float32))
            groups = (b, c.n_groups, c.ssm_state_size)
            return (y[:, :inner],
                    y[:, inner:inner + c.n_groups * c.ssm_state_size].reshape(
                        groups),
                    y[:, inner + c.n_groups * c.ssm_state_size:].reshape(
                        groups),
                    taps[:, ch:])

    def _lanes(self, x):
        """`[B, H]` -> `[B, H / g, g * P]`: a head's number over the
        head's own lanes."""
        b = x.shape[0]
        return jnp.repeat(x.reshape(b, -1, self._group),
                          self.cfg.mamba_head_dim, axis=-1)

    def _rows_of_lanes(self, x):
        """`[B, n_groups, ...]` -> `[B, H / g, ...]`: a group's numbers
        for each row of lanes its heads lie in."""
        return jnp.repeat(x, self._state_shape[0] // self.cfg.n_groups,
                          axis=1)

    def _ssm_inputs(self, x, bm, cm, dt, a):
        """What the update takes beside the state: `B` and `C` for each
        row of lanes `[B, H / g, N]`, and over the lanes `dt x`, `a` and
        `B . C`, `[B, H / g, g * P]` each."""
        b = x.shape[0]
        lanes = self._state_shape[-1]
        kq = self._rows_of_lanes((bm * cm).sum(-1))
        return (self._rows_of_lanes(bm), self._rows_of_lanes(cm),
                self._lanes(dt) * x.reshape(b, -1, lanes), self._lanes(a),
                jnp.broadcast_to(kq[..., None], kq.shape + (lanes,)))

    def _ssm_cell(self, p, s, taps, xbc, dt, a):
        """One position a row: the conv over the row's taps and this
        position's input `xbc` `[B, channels]`, then Mamba-2's update on
        the state `s` `[B, H / g, N, g * P]` as it rests; `taps` `[B, (K
        - 1) * channels]`. -> (`y` `[B, I]`, the next state, the next
        taps, the largest magnitude a row's state held `[B]`)."""
        b = xbc.shape[0]
        x, bm, cm, taps = self._ssm_conv(p, taps, xbc)
        with jax.named_scope("ssm_state"):
            kw, qw, write, decay, kq = self._ssm_inputs(x, bm, cm, dt, a)
            # S <- a S + B (dt x)^T; y = S^T C = a S^T C + (B . C) dt x:
            # ONE pass over S as it was found gives S^T C and its largest
            # magnitude, a second writes the next S
            sq, largest = jax.lax.reduce(
                (s * qw[..., None], jnp.abs(s)),
                (jnp.float32(0), jnp.float32(0)),
                lambda u, v: (u[0] + v[0], jnp.maximum(u[1], v[1])), (2,))
            o = decay * sq + kq * write
            s = decay[:, :, None, :] * s + kw[..., None] * write[:, :, None, :]
        return (self._skip(p, o.reshape(b, -1), x), s, taps,
                largest.reshape(b, -1).max(1))

    def _skip(self, p, y, x):
        """`y + D x`, a head's `D` over its lanes."""
        return y + x * jnp.repeat(p["D"], self.cfg.mamba_head_dim)

    def _ssm_rows(self, p, table, dev, taps, xbc, dt, a):
        """`_ssm_cell` on rows `dev` of a layer's state `table`, each read
        out of the row it rests in and written back into it by one kernel
        (ops/state_kernel.py, its decay-and-write rule): the same lines,
        the state never gathered. -> (the table, `y`, the next taps, the
        largest magnitude a row's state held, 0 for padding, and how many
        live rows were updated where they rested)."""
        b = xbc.shape[0]
        x, bm, cm, taps = self._ssm_conv(p, taps, xbc)
        with jax.named_scope("ssm_state"):
            kw, qw, write, decay, kq = self._ssm_inputs(x, bm, cm, dt, a)
            keys = jnp.stack([kw, qw], 1).swapaxes(2, 3)
            table, y, held = state_kernel.update_rows(
                table, dev, keys, jnp.stack([write, decay, kq], 1))
        return (table, self._skip(p, y.reshape(b, -1), x), taps, held,
                (dev < table.shape[0] - 1).sum(dtype=jnp.int32))

    def _ssm_out(self, p, x, y, z):
        """`y * SiLU(z)` normed a group at a time, through `W_out`, onto
        the residual stream: `x`'s next value."""
        c = self.cfg
        with jax.named_scope("ssm_out"):
            g = y * jax.nn.silu(z)
            shape = g.shape
            g = g.reshape(shape[:-1] + (c.n_groups, -1))
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                                  + c.layer_norm_epsilon)
            return x + self._mm(g.reshape(shape) * p["ssm_norm"], p["out"])

    def _ssm_decode(self, p, x, state, taps):
        """A Mamba-2 layer on `x` `[B, hidden]`, one event a row; `state`
        and `taps` are the layer's two leaves in turn
        (scoring/stream.py, `RowsInTurn`). The state is updated where it
        rests on a TPU, where its rows fit the kernel
        (ops/state_kernel.py `fits`); elsewhere its rows are read,
        stepped by `_ssm_cell` and written whole: one algorithm, and the
        plain path is the kernel's twin in the tests. -> (x, `a` `[B,
        H]`, the largest magnitude a row's `s` held `[B]`, the rows
        updated where they rested)."""
        z, xbc, dt, a = self._ssm_project(
            p, rms(x, p["norm"], self.cfg.rms_norm_eps))
        tapped = taps.read(x).reshape(x.shape[0], -1)

        def plain(table, dev, taps, xbc, dt, a):
            # the ring's own gather and scatter, as a function of the
            # table: a turn of `state`'s kind over the table handed in
            rows = type(state)(table, dev)
            y, s, taps, held = self._ssm_cell(p, rows.read(xbc), taps, xbc,
                                              dt, a)
            y = rows.write(s, y)
            return rows.table, y, taps, held, jnp.int32(0)

        def turn(table, dev):
            args = (table, dev, tapped, xbc, dt, a)
            if not state_kernel.fits(table.shape, table.dtype):
                return plain(*args)
            return jax.lax.platform_dependent(
                *args, default=plain,
                tpu=functools.partial(self._ssm_rows, p))

        y, c1, held, in_place = state.update(turn, x)
        x = taps.write(c1.reshape((-1,) + self._taps_shape),
                       self._ssm_out(p, x, y, z))
        return x, a, held, in_place

    def _ssm_prefill(self, p, x, count):
        """Over `[n, S, hidden]`: the cell scanned over the positions, a
        row's state and taps held where they are from its `count` on.
        -> (x, the state after position `count - 1`, the taps then)."""
        c = self.cfg
        n, s_len, _ = x.shape
        z, xbc, dt, a = self._ssm_project(p, rms(x, p["norm"],
                                                 c.rms_norm_eps))

        def position(carry, at):
            s, taps = carry
            xbc_t, dt_t, a_t, t = at
            y, s1, taps1, _ = self._ssm_cell(p, s, taps, xbc_t, dt_t, a_t)
            live = t < count
            return (jnp.where(live[:, None, None, None], s1, s),
                    jnp.where(live[:, None], taps1, taps)), y

        start = (jnp.zeros((n,) + self._state_shape, jnp.float32),
                 jnp.zeros((n, math.prod(self._taps_shape)),
                           c.compute_dtype))
        (s, taps), y = jax.lax.scan(
            position, start, (xbc.swapaxes(0, 1), dt.swapaxes(0, 1),
                              a.swapaxes(0, 1), jnp.arange(s_len)))
        return self._ssm_out(p, x, y.swapaxes(0, 1), z), s, taps

    # -- the attention layer --------------------------------------------------

    def _attention(self, p, x, attend):
        """An attention layer on the residual stream `x` `[..., hidden]`;
        `attend(q, k, v)` is the form. -> (x, the stored keys, the
        stored values)."""
        c = self.cfg
        cdt = c.compute_dtype
        u = rms(x, p["norm"], c.rms_norm_eps)
        with jax.named_scope("gqa_project"):
            q = self._mm(u, p["q"])
            k = self._mm(u, p["k"]).astype(cdt)
            v = self._mm(u, p["v"]).astype(cdt)
        with jax.named_scope("attn_full"):
            a = attend(q.reshape(q.shape[:-1] + (c.num_attention_heads,
                                                 c.head_dim)), k, v)
            return x + self._mm(a.reshape(q.shape), p["o"]), k, v

    # -- the latent expert layer ----------------------------------------------

    def _moe(self, p, x, live):
        """An expert layer on `x` `[T, hidden]`: the router on the full
        width, the held experts and their weighted sum in the latent
        width, the sum back up, the shared expert on the full width. ->
        (x, the held experts' token counts `[held]`)."""
        u = rms(x, p["norm"], self.cfg.rms_norm_eps)
        with jax.named_scope("moe_route"):
            idx, w = self.route(p["router"], u)
        with jax.named_scope("latent_down"):
            xl = self._mm(u, p["latent_down"])
        with jax.named_scope("moe_experts"):
            routed, counts = self._routed(p["experts"], xl, idx, w, live)
        with jax.named_scope("latent_up"):
            y = self._mm(routed, p["latent_up"])
        with jax.named_scope("shared_expert"):
            return x + y + self._mlp(p["shared"], u), counts

    # -- the stack ------------------------------------------------------------

    def _prefill(self, params, tokens, count):
        """Every layer over `[n, S]` tokens: (hidden states before the
        final norm `[n, S, hidden]`, what a layer leaves a row: a Mamba-2
        layer its state and taps after position `count - 1`, an
        attention layer its keys and values `[n, S, kv * d]`, an expert
        layer nothing)."""
        c = self.cfg
        n, s_len = tokens.shape
        x = params["embed"][tokens].astype(jnp.float32)
        left = []
        for l in range(self.layers):
            p = params[f"layer{l}"]
            kind = self.kinds[l]
            if kind == MAMBA:
                x, *rest = self._ssm_prefill(p, x, count)
            elif kind == ATTENTION:
                x, *rest = self._attention(
                    p, x, lambda q, k, v: self._causal_prefill(
                        q, k, v, count, c.num_key_value_heads))
            else:
                flat, _ = self._moe(p, x.reshape(n * s_len, -1),
                                    jnp.ones(n * s_len, bool))
                x, rest = flat.reshape(x.shape), []
            left.append(rest)
        return x, left

    def _leaves(self, layer: int) -> tuple:
        """The names of the leaves a layer keeps a row."""
        kind = self.kinds[layer]
        return ((f"s{layer}", f"c{layer}") if kind == MAMBA
                else (f"k{layer}", f"v{layer}") if kind == ATTENTION
                else ())

    def init_state(self, cap: int) -> dict:
        c = self.cfg
        state = self._row_state(cap)
        for l in range(self.layers):
            names = self._leaves(l)
            if self.kinds[l] == MAMBA:
                state[names[0]] = jnp.zeros((cap,) + self._state_shape,
                                            jnp.float32)
                state[names[1]] = jnp.zeros((cap,) + self._taps_shape,
                                            c.compute_dtype)
            for name in names if self.kinds[l] == ATTENTION else ():
                state[name] = jnp.zeros(
                    (cap, c.context_positions, c.kv_width), c.compute_dtype)
        return state

    def step_score(self, params: dict, rows: dict, v: jax.Array,
                   live: jax.Array):
        """One event a row: the score of the bin that arrived, then the
        row's next state. A Mamba-2 layer's `s` and `c` come in turn
        (scoring/stream.py, `RowsInTurn`): read, or updated where they
        rest, when the layer starts, written before the next one
        starts; an attention layer's window leaves as `ContextAtRest`s:
        the layer appends its ONE entry a row and reads the table behind
        it (`_decode_at_rest`); nothing is returned for either. Also the
        step's numbers, in `step_stats`' order (`live` masks the padding
        out of them): the expert layers' counts, the mean position, the
        mean of `a` over live rows, heads and Mamba-2 layers, the
        largest `|S|` found in the live rows' states, and the rows
        updated where they rested."""
        c = self.cfg
        pos = rows["pos"]
        token, score, out = self._arrive(params, rows, v)
        x = params["embed"][token].astype(jnp.float32)
        held = busiest = one_tile = at_rest = read = jnp.int32(0)
        in_place = jnp.int32(0)
        decay, largest = jnp.float32(0), jnp.float32(0)
        for l in range(self.layers):
            p = params[f"layer{l}"]
            kind = self.kinds[l]
            if kind == MAMBA:
                # the layer's rows when it starts, back in the table
                # before the next one starts
                x, a, most, rested = self._ssm_decode(
                    p, x, rows[f"s{l}"], rows[f"c{l}"])
                decay += jnp.where(live[:, None], a, 0).sum()
                largest = jnp.maximum(largest,
                                      jnp.where(live, most, 0).max())
                in_place += rested
            elif kind == ATTENTION:
                kctx, vctx = rows[f"k{l}"], rows[f"v{l}"]
                x, _, _ = self._attention(
                    p, x, lambda q, k, v, kctx=kctx, vctx=vctx:
                    self._decode_at_rest(q, k, v, kctx, vctx, pos,
                                         c.num_key_value_heads))
                at_rest += kctx.read_rows
                read += kctx.read_positions
            else:
                x, counts = self._moe(p, x, live)
                held += counts.sum()
                busiest = jnp.maximum(busiest, counts.max())
                one_tile += runs_one_tile(counts)
        out["hn"] = rms(x, params["norm"], c.rms_norm_eps).astype(
            c.compute_dtype)
        n_live = jnp.maximum(live.sum(), 1)
        stats = jnp.stack([
            held.astype(jnp.float32),
            (live.sum() * c.num_experts_per_tok
             * self.kinds.count(EXPERTS)).astype(jnp.float32),
            busiest.astype(jnp.float32),
            jnp.where(live, pos, 0).sum() / n_live,
            one_tile.astype(jnp.float32),
            at_rest.astype(jnp.float32), read.astype(jnp.float32),
            decay / (n_live * max(self.kinds.count(MAMBA), 1)
                     * c.mamba_num_heads),
            largest, in_place.astype(jnp.float32)])
        return score, out, stats

    def warm_state(self, params: dict, x: jax.Array, valid: jax.Array) -> dict:
        """State of `n` devices after their stored windows (`[n, W]`
        chronological left-padded): the prefill form over each window."""
        if state_kernel.fits((1,) + self._state_shape, jnp.float32):
            # traced once, when seeding starts: the step's kernel will want
            # its library (models/olmo_hybrid.py)
            state_kernel.import_ahead()
        state, left, _ = self._warm(params, x, valid)
        w = x.shape[1]
        for l, rest in enumerate(left):
            names = self._leaves(l)
            if self.kinds[l] == MAMBA:
                state[names[0]] = rest[0]
                state[names[1]] = rest[1].reshape(state[names[1]].shape)
            elif self.kinds[l] == ATTENTION:
                for name, entry in zip(names, rest):
                    state[name] = state[name].at[:, :w].set(entry)
        return state
