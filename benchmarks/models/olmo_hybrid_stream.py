"""`olmo-hybrid-stream`: the plain reference of the Olmo-Hybrid-7B
streaming scorer, written from the model's equations, and what one
scored event needs of the chip, counted from those equations.

It imports nothing of the program and takes nothing the program made:
weights come from `tenant_params(seed)`, history and frames from
benchmarks/gen.py. `model_config` is the published config.json's keys
(allenai/Olmo-Hybrid-7B) plus the scorer's own two sizes (`window`,
`context_positions`). The tokens, the statistics and the score are the
family's, and so are their few lines here: taken from
benchmarks/models/dsv3_stream.py, which states them.

What is computed: the FULL causal forward pass over each device's whole
sequence, history and every served tick. No cache, no state carried
between events: a linear layer is the plain loop over a sequence's
positions from `S = 0`, a head's state `[dk, dv]` on its own; its conv
is a left-padded convolution over the sequence; a full layer is one
masked softmax.

One token `x` at position `t` (`eps` = `rms_norm_eps`), layer by layer;
the norms follow what they norm (the Olmo-2 and -3 family's order):

    linear_attention (H = linear_num_value_heads heads, dk =
    linear_key_head_dim, dv = linear_value_head_dim, K =
    linear_conv_kernel_dim):
        z = [x Wq | x Wk | x Wv], rounded to the type it would rest in
        y = SiLU(sum_{j < K} conv[j] * z_{t - K + 1 + j}),  z = 0 before 0
        q, k, v <- y;  per head q = q / ||q|| * dk^-1/2, k = k / ||k||,
            ||.|| = sqrt(sum of squares + 1e-6)
        beta = (2 if linear_allow_neg_eigval else 1) * sigmoid(x Wb)
        alpha = exp(-exp(A_log) * softplus(x Wa + dt_bias))
        S <- alpha S;  r = v - S^T k;  S <- S + k (beta r)^T;  o = S^T q
        m = concat_h(RMSNorm_dv(o_h) * SiLU(x Wg)_h) Wo
    full_attention (n = num_attention_heads heads of d = hidden / n, a
    key-value head each, no rotary turn: rope_theta is null):
        q = RMSNorm(x Wq), k = RMSNorm(x Wk) over all n * d; v = x Wv
        m = concat_h(softmax(q_h K_h^T / sqrt(d)) V_h over j <= t) Wo
    x = x + RMSNorm(m);  x = x + RMSNorm(W_down(silu(x W_gate) * (x W_up)))

A device's sequence: its last `window` stored values, then every event
it was fed; one whose sequence has reached `context_positions` starts
again from its last `window` stored values, recurrent state and all
(dsv3_stream.py has the rule in full). `run(..., compute_dtype)` rounds
the two operands of every matrix product to `compute_dtype` and
accumulates in float32; everything else is float32: the conv (on inputs
that rest in bfloat16, or in float32 where the products are float32),
the recurrence and its state, norms, softmax, gates, residual stream,
score.

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
    _mlp,
    _normal,
    _rms,
    _window_tokens,
)

BLOCK_ROWS = 8            # sequences forwarded at once: their logits over
                          # the whole vocabulary are 0.9 GB at 272 positions
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


# -- weights ------------------------------------------------------------------

def _widths(mc: dict) -> tuple[int, int, int, int]:
    """(heads, dk, dv, the conv's channels) of a linear layer."""
    heads, dk = mc["linear_num_value_heads"], mc["linear_key_head_dim"]
    dv = mc["linear_value_head_dim"]
    return heads, dk, dv, 2 * mc["linear_num_key_heads"] * dk + heads * dv


def _block_shapes(mc: dict, layer: int) -> dict:
    h, w = mc["hidden_size"], jnp.bfloat16
    inter = mc["intermediate_size"]
    block = {"mixer_norm": ((h,), F32), "mlp_norm": ((h,), F32),
             "mlp": {"gate": ((h, inter), w), "up": ((h, inter), w),
                     "down": ((inter, h), w)}}
    if mc["layer_types"][layer] == FULL:
        block.update({"q": ((h, h), w), "k": ((h, h), w), "v": ((h, h), w),
                      "o": ((h, h), w), "q_norm": ((h,), F32),
                      "k_norm": ((h,), F32)})
        return block
    heads, dk, dv, channels = _widths(mc)
    keys = mc["linear_num_key_heads"] * dk
    block.update({
        "q": ((h, keys), w), "k": ((h, keys), w), "v": ((h, heads * dv), w),
        "g": ((h, heads * dv), w), "o": ((heads * dv, h), w),
        "a": ((h, heads), w), "b": ((h, heads), w),
        "conv": ((mc["linear_conv_kernel_dim"], channels), w),
        "A_log": ((heads,), F32), "dt_bias": ((heads,), F32),
        "o_norm": ((dv,), F32)})
    return block


def param_shapes(mc: dict) -> dict:
    """name -> (shape, dtype), laid out as the program's checkpoint."""
    h, vocab = mc["hidden_size"], mc["vocab_size"]
    shapes = {"embed": ((vocab, h), jnp.bfloat16), "norm": ((h,), F32),
              "head": ((h, vocab), jnp.bfloat16)}
    for layer in range(mc["num_hidden_layers"]):
        shapes[f"layer{layer}"] = _block_shapes(mc, layer)
    return shapes


def tenant_params(seed: int, tenant: int, model_config: dict) -> dict:
    """Tenant `tenant`'s weights in a run of `--seed seed`, on the device,
    a leaf at a time: every matrix (the conv's taps too) normal with std
    0.02 in bfloat16, norms 1; a linear layer's two vectors as the family
    draws them: `A_log = log(A)`, `A` uniform in (0, 16); `dt_bias` the
    inverse softplus of a step `dt` log-uniform in (0.001, 0.1)."""
    gc.collect()            # what a stopped runtime still held goes first
    key = jax.random.PRNGKey((int(seed) % (2 ** 32) + tenant) % (2 ** 32))
    made = [0]

    def build(spec, name=""):
        if isinstance(spec, dict):
            return {k: build(v, k) for k, v in spec.items()}
        shape, dtype = spec
        if "norm" in name:
            return jnp.ones(shape, dtype)
        made[0] += 1
        k = jax.random.fold_in(key, made[0])
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, F32, 1e-3, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, F32, np.log(0.001),
                                            np.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return _normal(k, shape, dtype, 0.02)

    return build(param_shapes(model_config))


# -- the equations ------------------------------------------------------------

def _rests_in(cdt):
    """The type a conv input rests in beside products in `cdt`."""
    return F32 if jnp.dtype(cdt) == jnp.dtype(F32) else jnp.bfloat16


def _unit(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _linear_attention(p, x, mc: dict, cdt):
    """The gated delta rule over `x` `[n, S, hidden]`, position by
    position from an empty state."""
    n, s, _ = x.shape
    heads, dk, dv, channels = _widths(mc)
    taps = mc["linear_conv_kernel_dim"]
    z = jnp.concatenate([_ein("nsi,io->nso", x, p[w], cdt) for w in "qkv"],
                        -1).astype(_rests_in(cdt)).astype(F32)
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = p["conv"].astype(F32)
    y = jax.nn.silu(sum(padded[:, j:j + s] * conv[j] for j in range(taps)))
    keys = heads * dk
    q = _unit(y[..., :keys].reshape(n, s, heads, dk)) * dk ** -0.5
    k = _unit(y[..., keys:2 * keys].reshape(n, s, heads, dk))
    v = y[..., 2 * keys:].reshape(n, s, heads, dv)
    beta = jax.nn.sigmoid(_ein("nsi,ih->nsh", x, p["b"], cdt)) * (
        2.0 if mc["linear_allow_neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        _ein("nsi,ih->nsh", x, p["a"], cdt) + p["dt_bias"]))

    def position(state, at):            # state [n, heads, dk, dv]
        q_t, k_t, v_t, alpha_t, beta_t = at
        state = alpha_t[..., None, None] * state
        r = v_t - (state * k_t[..., None]).sum(2)
        state = state + k_t[..., None] * (beta_t[..., None] * r)[:, :, None]
        return state, (state * q_t[..., None]).sum(2)

    _, o = jax.lax.scan(
        position, jnp.zeros((n, heads, dk, dv), F32),
        tuple(a.swapaxes(0, 1) for a in (q, k, v, alpha, beta)))
    gate = jax.nn.silu(_ein("nsi,io->nso", x, p["g"], cdt))
    out = _rms(o.swapaxes(0, 1), p["o_norm"], mc["rms_norm_eps"]) \
        * gate.reshape(n, s, heads, dv)
    return _ein("nsi,io->nso", out.reshape(n, s, heads * dv), p["o"], cdt)


def _full_attention(p, x, mc: dict, cdt):
    """Multi-head attention over `x` `[n, S, hidden]`, queries and keys
    normed whole, no positional turn."""
    n, s, hidden = x.shape
    heads, eps = mc["num_attention_heads"], mc["rms_norm_eps"]
    d = hidden // heads
    q = _rms(_ein("nsi,io->nso", x, p["q"], cdt), p["q_norm"], eps)
    k = _rms(_ein("nsi,io->nso", x, p["k"], cdt), p["k_norm"], eps)
    v = _ein("nsi,io->nso", x, p["v"], cdt)
    q, k, v = (a.reshape(n, s, heads, d) for a in (q, k, v))
    logits = _ein("nqhd,nkhd->nhqk", q, k, cdt) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = _ein("nhqk,nkhd->nqhd", probs, v, cdt).reshape(n, s, hidden)
    return _ein("nsi,io->nso", out, p["o"], cdt)


def _block(p, x, layer: int, mc: dict, cdt):
    eps = mc["rms_norm_eps"]
    mixer = (_full_attention if mc["layer_types"][layer] == FULL
             else _linear_attention)
    x = x + _rms(mixer(p, x, mc, cdt), p["mixer_norm"], eps)
    return x + _rms(_mlp(p["mlp"], x, cdt), p["mlp_norm"], eps)


class _Forward:
    """The jitted pieces, a layer at a time (one compile for each layer,
    shape and precision)."""

    def __init__(self, mc: dict, cdt):
        self.mc, self.cdt = mc, cdt
        self.embed = jax.jit(lambda e, tok: e[tok].astype(F32))
        self.block = jax.jit(lambda p, x, layer: _block(p, x, layer, mc, cdt),
                             static_argnums=2)
        self.head = jax.jit(self._surprisal)

    def _surprisal(self, norm, head, x, tokens):
        """`[n, S]`: at position i, the surprisal of token i under the
        prediction at i - 1 (position 0: 0)."""
        logits = _ein("nsi,io->nso", _rms(x, norm, self.mc["rms_norm_eps"]),
                      head, self.cdt)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        got = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.concatenate([jnp.zeros((x.shape[0], 1), F32), -got], 1)

    def hidden(self, params, tokens):
        x = self.embed(params["embed"], tokens)
        for layer in range(self.mc["num_hidden_layers"]):
            x = self.block(params[f"layer{layer}"], x, layer)
        return x


# -- a run ----------------------------------------------------------------------

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
    vocab, gate = mc["vocab_size"], max(8, window // 8)
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


# -- what an event needs of the chip ----------------------------------------

FRAME_EVENTS = 256        # the step the byte count spreads the weights over


def _matrix_params(mc: dict) -> float:
    """Parameters in a token's matrix products: every layer's and the
    head's (the embedding is a table of which a token reads a row)."""
    h, inter = mc["hidden_size"], mc["intermediate_size"]
    heads, dk, dv, channels = _widths(mc)
    linear = (h * channels + 2 * h * heads * dv + 2 * h * heads
              + mc["linear_conv_kernel_dim"] * channels)
    per_kind = {LINEAR: linear, FULL: 4 * h * h}
    return float(sum(per_kind[kind] + 3 * h * inter
                     for kind in mc["layer_types"][:mc["num_hidden_layers"]])
                 + h * mc["vocab_size"])


def _kinds(mc: dict) -> tuple[int, int]:
    kinds = mc["layer_types"][:mc["num_hidden_layers"]]
    return kinds.count(LINEAR), kinds.count(FULL)


def _mean_positions(mc: dict) -> float:
    """Positions an event of a full layer attends to (its own among
    them), averaged over a run that goes from a seeded window to a full
    context."""
    return float((np.arange(mc["window"], mc["context_positions"]) + 1).mean())


def state_row_bytes(mc: dict) -> tuple[int, int]:
    """A device's recurrent state at rest, in bytes: (the matrix states,
    float32; the conv's taps, 2 B a value)."""
    heads, dk, dv, channels = _widths(mc)
    linear, _ = _kinds(mc)
    return (linear * 4 * heads * dk * dv,
            linear * 2 * (mc["linear_conv_kernel_dim"] - 1) * channels)


def flops_per_event(model_config: dict) -> float:
    """2 FLOPs a parameter the token's products touch (every layer's
    projections, conv and MLP, the head over the whole vocabulary), a
    linear layer's recurrence (a multiply-add an element of `S` for each
    of `S^T k`, the outer product and `S^T q`, a multiply for the decay:
    7 a value of the state), and a full layer's attention over its
    context: 2 x heads x head_dim for the logits and as much for the
    weighted sum, a position."""
    mc = model_config
    heads, dk, dv, _ = _widths(mc)
    linear, full = _kinds(mc)
    return (2.0 * _matrix_params(mc) + linear * 7.0 * heads * dk * dv
            + full * 4.0 * mc["hidden_size"] * _mean_positions(mc))


def bytes_per_event(model_config: dict, score_dtype: str) -> float:
    """What any implementation must move: the weights of the products
    once a step of `FRAME_EVENTS` events (2 B a parameter; the signature
    has no frame size, so the count assumes the configuration's frame of
    256), the token's row of the embedding, plus the event's own state:
    every matrix state and every tap read ONCE and written ONCE, whatever
    the program does; a full layer's context read once (keys and values,
    2 B a value) but for its own position, which is written; its `hn` read and
    written, its value in and its score out."""
    mc = model_config
    _, full = _kinds(mc)
    entry = 2 * 2.0 * mc["hidden_size"]
    return (2.0 * _matrix_params(mc) / FRAME_EVENTS
            + 2.0 * mc["hidden_size"]
            + 2.0 * sum(state_row_bytes(mc))
            + full * entry * _mean_positions(mc)
            + 2 * 2.0 * mc["hidden_size"] + 8
            + jnp.dtype(score_dtype).itemsize)
