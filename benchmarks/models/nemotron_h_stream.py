"""`nemotron-h-stream`: the plain reference of the
NVIDIA-Nemotron-3-Super-120B-A12B streaming scorer, written from the
model's equations, and what one scored event needs of the chip, counted
from those equations.

It imports nothing of the program and takes nothing the program made:
weights come from `tenant_params(seed)`, history and frames from
benchmarks/gen.py. `model_config` is the published config.json's keys
(nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16) plus the share held here
(`first_expert`, `n_routed_experts_held`, `vocab_held`) and the
scorer's own two sizes (`window`, `context_positions`). The tokens, the
statistics and the score are the family's, and so are their few lines
here: taken from benchmarks/models/dsv3_stream.py, which states them.

What is computed: the FULL causal forward pass over each device's whole
sequence, history and every served tick. No cache, no state carried
between events, no kernel, no grouping: a Mamba-2 layer is the plain
loop over a sequence's positions from `S = 0`, a head's state `[P, N]`
on its own; its conv is a left-padded convolution over the sequence; an
attention layer is one masked softmax; every held expert runs over every
token with its weight (0 where the token did not choose it).

One token at position `t`, layer by layer, each layer `x = x +
mixer(RMSNorm(x))` of the kind `hybrid_override_pattern[l]` names (`eps`
= `layer_norm_epsilon`):

    M (H = mamba_num_heads heads of P = mamba_head_dim, I = H P, N =
    ssm_state_size in G = n_groups groups, K = conv_kernel):
        (z, xBC, dt) = split(u W_in), xBC rounded to the type it would rest in
        xBC = SiLU(sum_{j < K} conv[j] * xBC_{t - K + 1 + j} + conv_bias)
        x, B, C <- xBC;  dt = softplus(dt + dt_bias);  a = exp(-dt exp(A_log))
        S_h <- a_h S_h + (dt_h x_h) B_g^T;  y_h = S_h C_g + D_h x_h,
            g = h // (H / G)
        m = (RMSNorm over G groups of I / G (y * SiLU(z)) * w) W_out
    E: s = sigmoid(u W_r^T) in float32 (precision HIGHEST); the
        num_experts_per_tok largest of s + b; weight = chosen s / their
        sum * routed_scaling_factor;  x_l = u W_dl;
        m = (sum over the chosen experts HELD HERE of
             weight * relu(x_l U_e)^2 V_e) W_ul + relu(u S_u)^2 S_d:
        what the absent experts would add is left out
    * (num_attention_heads query heads on num_key_value_heads of
    head_dim, no rotary turn, no norm on q or k):
        m = concat_h(softmax(q_h K_g^T / sqrt(head_dim)) V_g over j <= t) W_o

then a final RMSNorm and the head over the held vocabulary.

A device's sequence: its last `window` stored values, then every event
it was fed; one whose sequence has reached `context_positions` starts
again from its last `window` stored values, recurrent state and all
(dsv3_stream.py has the rule in full). `run(..., compute_dtype)` rounds
the two operands of every matrix product to `compute_dtype` and
accumulates in float32; everything else is float32: the conv (on inputs
that rest in bfloat16, or in float32 where the products are float32),
the recurrence and its state, norms, softmax, router, gates, residual
stream, score.

It runs devices in blocks of `BLOCK_ROWS` sequences and a layer at a
time, so it fits beside the weights once the runtime has stopped.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.models.dsv3_stream import (
    F32,
    SCORE_CLIP,
    _ein,
    _event_tokens,
    _held,
    _normal,
    _rms,
    _window_tokens,
)

BLOCK_ROWS = 16           # sequences forwarded at once: their held
                          # experts' products are 77 MB each at 448
                          # positions, a layer's states 64 MiB
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _kinds(mc: dict) -> list:
    return list(mc["hybrid_override_pattern"][:mc["num_hidden_layers"]])


# -- weights ------------------------------------------------------------------

def _widths(mc: dict) -> tuple[int, int, int]:
    """(I, the conv's channels, H) of a Mamba-2 layer."""
    inner = mc["mamba_num_heads"] * mc["mamba_head_dim"]
    return (inner, inner + 2 * mc["n_groups"] * mc["ssm_state_size"],
            mc["mamba_num_heads"])


def _block_shapes(mc: dict, layer: int) -> dict:
    h, w = mc["hidden_size"], jnp.bfloat16
    kind = _kinds(mc)[layer]
    block = {"norm": ((h,), F32)}
    if kind == MAMBA:
        inner, channels, heads = _widths(mc)
        block.update({
            "in": ((h, inner + channels + heads), w),
            "conv": ((mc["conv_kernel"], channels), w),
            "conv_bias": ((channels,), w), "A_log": ((heads,), F32),
            "dt_bias": ((heads,), F32), "D": ((heads,), F32),
            "ssm_norm": ((inner,), F32), "out": ((inner, h), w)})
    elif kind == ATTENTION:
        width = mc["num_attention_heads"] * mc["head_dim"]
        kv = mc["num_key_value_heads"] * mc["head_dim"]
        block.update({"q": ((h, width), w), "k": ((h, kv), w),
                      "v": ((h, kv), w), "o": ((width, h), w)})
    else:
        latent, e = mc["moe_latent_size"], mc["n_routed_experts"]
        inner, shared = (mc["moe_intermediate_size"],
                         mc["moe_shared_expert_intermediate_size"])
        block.update({
            "router": {"w": ((e, h), F32), "bias": ((e,), F32)},
            "latent_down": ((h, latent), w), "latent_up": ((latent, h), w),
            "experts": {f"e{i}": {"up": ((latent, inner), w),
                                  "down": ((inner, latent), w)}
                        for i in range(_held(mc)[0])},
            "shared": {"up": ((h, shared), w), "down": ((shared, h), w)}})
    return block


def param_shapes(mc: dict) -> dict:
    """name -> (shape, dtype), laid out as the program's checkpoint."""
    h, vocab = mc["hidden_size"], _held(mc)[1]
    shapes = {"embed": ((vocab, h), jnp.bfloat16), "norm": ((h,), F32),
              "head": ((h, vocab), jnp.bfloat16)}
    for layer in range(mc["num_hidden_layers"]):
        shapes[f"layer{layer}"] = _block_shapes(mc, layer)
    return shapes


def tenant_params(seed: int, tenant: int, model_config: dict) -> dict:
    """Tenant `tenant`'s weights in a run of `--seed seed`, on the device,
    a leaf at a time: every matrix normal with std 0.02 in bfloat16 (the
    router float32, its bias std 0.01), norms 1; a Mamba-2 layer's as the
    family draws them: the conv's taps and bias uniform in `+-K^-1/2`,
    `A_log = log(A)`, `A` uniform in (1, 16); `dt_bias` the inverse
    softplus of a step `dt` log-uniform in (`time_step_min`,
    `time_step_max`), floored at `time_step_floor`; `D` 1."""
    gc.collect()            # what a stopped runtime still held goes first
    mc = model_config
    key = jax.random.PRNGKey((int(seed) % (2 ** 32) + tenant) % (2 ** 32))
    made = [0]
    bound = mc["conv_kernel"] ** -0.5

    def build(spec, name=""):
        if isinstance(spec, dict):
            return {k: build(v, k) for k, v in spec.items()}
        shape, dtype = spec
        if "norm" in name:
            return jnp.ones(shape, dtype)
        if name == "D":
            return jnp.ones(shape, dtype)
        made[0] += 1
        k = jax.random.fold_in(key, made[0])
        if name in ("conv", "conv_bias"):
            return jax.random.uniform(k, shape, F32, -bound,
                                      bound).astype(dtype)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, F32, np.log(mc["time_step_min"]),
                np.log(mc["time_step_max"]))), mc["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        return _normal(k, shape, dtype, 0.01 if name == "bias" else 0.02)

    return build(param_shapes(mc))


# -- the equations ------------------------------------------------------------

def _rests_in(cdt):
    """The type a conv input rests in beside products in `cdt`."""
    return F32 if jnp.dtype(cdt) == jnp.dtype(F32) else jnp.bfloat16


def _mamba(p, u, mc: dict, cdt):
    """Mamba-2 over normed `u` `[n, S, hidden]`, position by position
    from an empty state."""
    n, s, _ = u.shape
    inner, channels, heads = _widths(mc)
    dim, groups, taps = mc["mamba_head_dim"], mc["n_groups"], mc["conv_kernel"]
    zxd = _ein("nsi,io->nso", u, p["in"], cdt)
    z, xbc = zxd[..., :inner], zxd[..., inner:inner + channels]
    xbc = xbc.astype(_rests_in(cdt)).astype(F32)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = p["conv"].astype(F32)
    y = jax.nn.silu(sum(padded[:, j:j + s] * conv[j] for j in range(taps))
                    + p["conv_bias"].astype(F32))
    x = y[..., :inner].reshape(n, s, heads, dim)
    bc = y[..., inner:].reshape(n, s, 2, groups, -1)
    b, c = (jnp.repeat(bc[:, :, i], heads // groups, axis=2) for i in (0, 1))
    dt = jax.nn.softplus(zxd[..., inner + channels:] + p["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))

    def position(state, at):            # state [n, heads, dim, N]
        x_t, b_t, c_t, dt_t, a_t = at
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, (state * c_t[:, :, None, :]).sum(-1)

    _, out = jax.lax.scan(
        position, jnp.zeros((n, heads, dim, mc["ssm_state_size"]), F32),
        tuple(t.swapaxes(0, 1) for t in (x, b, c, dt, a)))
    out = out.swapaxes(0, 1) + p["D"][:, None] * x
    gated = (out.reshape(n, s, inner) * jax.nn.silu(z)).reshape(
        n, s, groups, -1)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                                  + mc["layer_norm_epsilon"])
    return _ein("nsi,io->nso", gated.reshape(n, s, inner) * p["ssm_norm"],
                p["out"], cdt)


def _attention(p, u, mc: dict, cdt):
    """Grouped-query attention over normed `u` `[n, S, hidden]`, no
    positional turn."""
    n, s, _ = u.shape
    heads, kv, d = (mc["num_attention_heads"], mc["num_key_value_heads"],
                    mc["head_dim"])
    q = _ein("nsi,io->nso", u, p["q"], cdt).reshape(n, s, kv, heads // kv, d)
    k = _ein("nsi,io->nso", u, p["k"], cdt).reshape(n, s, kv, d)
    v = _ein("nsi,io->nso", u, p["v"], cdt).reshape(n, s, kv, d)
    logits = _ein("nqkgd,nskd->nkgqs", q, k, cdt) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = _ein("nkgqs,nskd->nqkgd", probs, v, cdt).reshape(n, s, heads * d)
    return _ein("nsi,io->nso", out, p["o"], cdt)


def _relu2(p, x, cdt):
    return _ein("...i,io->...o", jnp.square(jax.nn.relu(
        _ein("...i,io->...o", x, p["up"], cdt))), p["down"], cdt)


def routing_weights(p, u, mc):
    """`[T, n_routed_experts]` float32: a token's weight for each routed
    expert, 0 where it did not choose it."""
    s = jax.nn.sigmoid(jnp.einsum(
        "ti,ei->te", u, p["w"], precision=jax.lax.Precision.HIGHEST))
    choice = s + p["bias"]
    bar = jnp.sort(choice, axis=-1)[:, -mc["num_experts_per_tok"]][:, None]
    chosen = jnp.where(choice >= bar, s, 0.0)
    return chosen / chosen.sum(-1, keepdims=True) * mc["routed_scaling_factor"]


def expert_layer(p, u, mc, cdt):
    """The held experts' part in the latent width, back up, plus the
    shared expert, for normed `u` `[T, hidden]`."""
    first, (held, _) = mc.get("first_expert", 0), _held(mc)
    weights = routing_weights(p["router"], u, mc)
    latent = _ein("ti,io->to", u, p["latent_down"], cdt)
    routed = jnp.zeros_like(latent)
    for e in range(held):
        routed = routed + weights[:, first + e, None] * _relu2(
            p["experts"][f"e{e}"], latent, cdt)
    return (_ein("ti,io->to", routed, p["latent_up"], cdt)
            + _relu2(p["shared"], u, cdt))


def _block(p, x, kind: str, mc: dict, cdt):
    u = _rms(x, p["norm"], mc["layer_norm_epsilon"])
    if kind == MAMBA:
        return x + _mamba(p, u, mc, cdt)
    if kind == ATTENTION:
        return x + _attention(p, u, mc, cdt)
    n, s, hid = u.shape
    return x + expert_layer(p, u.reshape(n * s, hid), mc, cdt).reshape(
        n, s, hid)


class _Forward:
    """The jitted pieces, a layer at a time (one compile for each kind of
    layer, shape and precision)."""

    def __init__(self, mc: dict, cdt):
        self.mc, self.cdt = mc, cdt
        self.embed = jax.jit(lambda e, tok: e[tok].astype(F32))
        self.block = jax.jit(lambda p, x, kind: _block(p, x, kind, mc, cdt),
                             static_argnums=2)
        self.head = jax.jit(self._surprisal)

    def _surprisal(self, norm, head, x, tokens):
        """`[n, S]`: at position i, the surprisal of token i under the
        prediction at i - 1 (position 0: 0)."""
        logits = _ein("nsi,io->nso",
                      _rms(x, norm, self.mc["layer_norm_epsilon"]), head,
                      self.cdt)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        got = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.concatenate([jnp.zeros((x.shape[0], 1), F32), -got], 1)

    def hidden(self, params, tokens):
        x = self.embed(params["embed"], tokens)
        for layer, kind in enumerate(_kinds(self.mc)):
            x = self.block(params[f"layer{layer}"], x, kind)
        return x


# -- a run --------------------------------------------------------------------

def run(params, hist: np.ndarray, frames: np.ndarray, fed: np.ndarray,
        model_config: dict, compute_dtype: str,
        block: int | None = None) -> np.ndarray:
    """Scores [T, D] float32 for ticks [T, D] (one event a device a tick,
    in order) after seeding from hist [D, >=W], or from nothing where
    hist is [D, 0]. `fed` [T, D] says which events the program was given:
    a device keeps its sequence as it is through a tick it was not fed.
    Forwards `block` sequences at a time; any `block` gives the same
    scores bit for bit."""
    mc = model_config
    window, cap = int(mc["window"]), int(mc["context_positions"])
    vocab, gate = _held(mc)[1], max(8, window // 8)
    ticks, devices = frames.shape
    block = block or BLOCK_ROWS
    every = np.arange(devices)
    # 1. each device's sequences as tokens: a list of [D, cap] rounds, a
    #    device's `seg` saying which round it is writing and `pos` where;
    #    `values` [D, .] is everything stored for it, `length` how much
    values = np.zeros((devices, hist.shape[1] + ticks), np.float32)
    values[:, :hist.shape[1]] = hist
    length = np.full(devices, hist.shape[1], np.int64)

    def last_window(rows):
        return values[rows[:, None],
                      length[rows, None] - window + np.arange(window)]

    rounds = [np.zeros((devices, cap), np.int32)]
    seg = np.zeros(devices, np.int64)
    pos = np.zeros(devices, np.int64)
    if hist.shape[1]:
        tok, mean, var = _window_tokens(jnp.asarray(last_window(every)),
                                        vocab=vocab)
        rounds[0][:, :window] = np.asarray(tok)
        pos[:] = window
        n = jnp.full(devices, window, jnp.int32)
    else:
        mean, var = jnp.zeros(devices, F32), jnp.ones(devices, F32)
        n = jnp.zeros(devices, jnp.int32)
    at = np.zeros((ticks, devices, 2), np.int64)      # (round, position)
    seen = np.zeros((ticks, devices), np.int64)       # values before it
    for t in range(ticks):
        given = np.asarray(fed[t], bool)
        tok, before, (mean, var, n) = _event_tokens(
            mean, var, n, jnp.asarray(frames[t], F32), jnp.asarray(given),
            vocab=vocab, window=window)
        tok, who = np.asarray(tok), every[given]
        for k in np.unique(seg[who]):
            rows = who[seg[who] == k]
            rounds[k][rows, pos[rows]] = tok[rows]
        at[t, :, 0], at[t, :, 1] = seg, pos
        seen[t] = np.asarray(before)
        values[who, length[who]] = frames[t][who]
        length[who] += 1
        pos[who] += 1
        full = every[pos >= cap]
        if full.size:       # start again from the last `window` stored values
            tok, m2, v2 = _window_tokens(jnp.asarray(last_window(full)),
                                         vocab=vocab)
            seg[full] += 1
            if seg[full].max() >= len(rounds):
                rounds.append(np.zeros((devices, cap), np.int32))
            rounds_of = seg[full]
            for k in np.unique(rounds_of):
                rounds[k][full[rounds_of == k], :window] = \
                    np.asarray(tok)[rounds_of == k]
            pos[full] = window
            mean, var = mean.at[full].set(m2), var.at[full].set(v2)
            n = n.at[full].set(window)
    # 2. the forward pass over every sequence that holds an event, as far
    #    as the longest of them goes (a round that was left is full)
    fwd = _Forward(mc, compute_dtype)
    long = cap if len(rounds) > 1 else min(cap, -(-int(pos.max()) // 16) * 16)
    surprisal = np.zeros((len(rounds), devices, cap), np.float32)
    for k, tokens in enumerate(rounds):
        used = every[(seg > k) | ((seg == k) & (pos > 0))]
        for lo in range(0, used.size, block):
            rows = used[lo:lo + block]
            padded = np.zeros((block, long), np.int32)    # one compiled shape
            padded[:rows.size] = tokens[rows, :long]
            x = fwd.hidden(params, jnp.asarray(padded))
            surprisal[k, rows, :long] = np.asarray(fwd.head(
                params["norm"], params["head"], x,
                jnp.asarray(padded)))[:rows.size]
    # 3. an event's score, read off the position before it
    out = surprisal[at[..., 0], every[None, :], at[..., 1]]
    out = np.where((seen >= gate) & (at[..., 1] > 0), out, 0.0)
    return np.clip(out, 0.0, SCORE_CLIP).astype(np.float32)


# -- what an event needs of the chip ------------------------------------------

FRAME_EVENTS = 128        # the step the byte count spreads the weights over


def _layer_params(mc: dict) -> dict:
    """Parameters in one layer's matrix products, by kind: (all held
    here, those one token's products touch: of the held experts its
    expected share)."""
    h = mc["hidden_size"]
    inner, channels, heads = _widths(mc)
    mamba = (h * (inner + channels + heads) + mc["conv_kernel"] * channels
             + inner * h)
    width = mc["num_attention_heads"] * mc["head_dim"]
    attention = 2 * h * width + 2 * h * mc["num_key_value_heads"] * mc[
        "head_dim"]
    held = _held(mc)[0]
    expert = 2 * mc["moe_latent_size"] * mc["moe_intermediate_size"]
    around = (h * mc["n_routed_experts"] + 2 * h * mc["moe_latent_size"]
              + 2 * h * mc["moe_shared_expert_intermediate_size"])
    chosen_here = mc["num_experts_per_tok"] * held / mc["n_routed_experts"]
    return {MAMBA: (mamba, mamba), ATTENTION: (attention, attention),
            EXPERTS: (around + held * expert, around + chosen_here * expert)}


def _matrix_params(mc: dict) -> tuple[float, float]:
    """Parameters in matrix products over the layers and the head:
    (all held here, the embedding's table too; those one token's
    products touch)."""
    per_kind = _layer_params(mc)
    head = mc["hidden_size"] * _held(mc)[1]
    kinds = _kinds(mc)
    return (float(sum(per_kind[k][0] for k in kinds) + 2 * head),
            float(sum(per_kind[k][1] for k in kinds) + head))


def _mean_positions(mc: dict) -> float:
    """Positions an event of an attention layer attends to (its own among
    them), averaged over a run that goes from a seeded window to a full
    context."""
    return float((np.arange(mc["window"], mc["context_positions"]) + 1).mean())


def state_row_bytes(mc: dict) -> tuple[int, int]:
    """A device's recurrent state at rest, in bytes: (the matrix states,
    float32; the conv's taps, 2 B a value)."""
    inner, channels, _ = _widths(mc)
    mamba = _kinds(mc).count(MAMBA)
    return (mamba * 4 * inner * mc["ssm_state_size"],
            mamba * 2 * (mc["conv_kernel"] - 1) * channels)


def flops_per_event(model_config: dict) -> float:
    """2 FLOPs a parameter the token's products touch (every layer's
    projections and conv, the router, the latent projections, the shared
    expert and the chosen experts held here, the head over the held
    vocabulary), a Mamba-2 layer's recurrence (a multiply for the decay
    and a multiply-add for the write an element of `S`, and a
    multiply-add for `S C`: 5 a value of the state), and an attention
    layer's over its context: 2 x heads x head_dim for the logits and as
    much for the weighted sum, a position."""
    mc = model_config
    inner, _, _ = _widths(mc)
    kinds = _kinds(mc)
    return (2.0 * _matrix_params(mc)[1]
            + kinds.count(MAMBA) * 5.0 * inner * mc["ssm_state_size"]
            + kinds.count(ATTENTION) * 4.0 * mc["num_attention_heads"]
            * mc["head_dim"] * _mean_positions(mc))


def bytes_per_event(model_config: dict, score_dtype: str) -> float:
    """What any implementation must move: every held weight once a step
    of `FRAME_EVENTS` events (2 B a parameter: the held experts are
    streamed whatever a frame routes; the signature has no frame size,
    so the count assumes the configuration's frame of 128), the token's
    row of the embedding, plus the event's own state: every matrix state
    and every tap read ONCE and written ONCE, whatever the program does;
    an attention layer's context read once (keys and values, 2 B a
    value) but for its own position, which is written; its `hn` read and
    written, its value in and its score out."""
    mc = model_config
    kv = mc["num_key_value_heads"] * mc["head_dim"]
    entry = 2 * 2.0 * kv
    return (2.0 * _matrix_params(mc)[0] / FRAME_EVENTS
            + 2.0 * mc["hidden_size"]
            + 2.0 * sum(state_row_bytes(mc))
            + _kinds(mc).count(ATTENTION) * entry * _mean_positions(mc)
            + 2 * 2.0 * mc["hidden_size"] + 8
            + jnp.dtype(score_dtype).itemsize)
