"""`lfm2-stream`: the plain reference of the LFM2-24B-A2B streaming
scorer, written from the model's equations, and what one scored event
needs of the chip, counted from those equations.

It imports nothing of the program and takes nothing the program made:
weights come from `tenant_params(seed)`, history and frames from
benchmarks/gen.py. `model_config` is the published config.json's keys
(LiquidAI/LFM2-24B-A2B, `model_type` `lfm2_moe`) plus `tie_embedding`
(the family's published configs; the catalog's row drops the key), the
share held here where one is (`first_expert`, `num_experts_held`) and
the scorer's own two sizes (`window`, `context_positions`). The tokens,
the statistics and the score are the family's, and so are their few
lines here: taken from benchmarks/models/dsv3_stream.py, which states
them.

What is computed: the FULL causal forward pass over each device's whole
sequence, history and every served tick. No cache, no state carried
between events, no grouped heads, no grouped products: a `conv`
operator is a left-padded depthwise convolution over the sequence, an
attention operator one masked softmax with a key-value head repeated
for the query heads that read it, the held experts are a loop, each
over every token with its weight (0 where the token did not choose it).

One token `x` at position `t` (`eps` = `norm_eps`); a layer is `x = x +
op(RMSNorm(x))`, then `x = x + ffn(RMSNorm(x))`, no projection has a
bias; after the last layer one more RMSNorm, then the head, which is
the embedding's matrix:

    conv (K = conv_L_cache taps), input u:
        (B, C, z) = split3(u W_in), hidden each, in that order
        s = B * z, rounded to the type it would rest in
        c_t = sum_{j < K} w[j] * s_{t - K + 1 + j},  s = 0 before 0
        op = (C * c_t) W_out                        (no activation)
    full_attention (n = num_attention_heads heads of d = hidden / n over
    kv = num_key_value_heads), input u:
        q = u Wq [n, d];  k = u Wk, v = u Wv [kv, d]
        q, k <- RMSNorm over d (a weight of d for q, one for k), then
            the rotary turn at t, theta = rope_parameters.rope_theta,
            all d dimensions, pairs (i, i + d / 2)
        op = concat_h(softmax(q_h K_{h // (n / kv)}^T / sqrt(d))
                      V_{h // (n / kv)} over j <= t) Wo
    ffn, the first num_dense_layers layers, input n:
        W_down(silu(n W_gate) * (n W_up)), width intermediate_size
    ffn, the others:
        s = sigmoid(n Wr^T) (float32) over num_experts; the
        num_experts_per_tok largest of s + b (b only where
        use_expert_bias, and in the choice only); weight = chosen s /
        (their sum + 1e-6) where norm_topk_prob, times
        routed_scaling_factor; the sum over the chosen experts HELD HERE
        of weight * expert(n), an expert a SiLU-gated MLP of
        moe_intermediate_size: what absent experts would add is left
        out. No shared expert.

A device's sequence: its last `window` stored values, then every event
it was fed; one whose sequence has reached `context_positions` starts
again from its last `window` stored values (dsv3_stream.py has the rule
in full). `run(..., compute_dtype)` rounds the two operands of every
matrix product to `compute_dtype` and accumulates in float32;
everything else is float32: the gates, the taps' sum (on inputs that
rest in bfloat16, or in float32 where the products are float32), norms,
softmax, router, residual stream, score.

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

BLOCK_ROWS = 16           # sequences forwarded at once: their logits over
                          # the whole vocabulary are 1.1 GB at 256 positions
CONV, FULL = "conv", "full_attention"
SUM_EPS = 1e-6            # the published denominator: the kept sum + 1e-6


# -- weights ------------------------------------------------------------------

def _held(mc: dict) -> int:
    return mc.get("num_experts_held") or mc["num_experts"]


def _heads(mc: dict) -> tuple[int, int, int]:
    """(query heads, key-value heads, a head's width)."""
    n = mc["num_attention_heads"]
    return n, mc["num_key_value_heads"], mc["hidden_size"] // n


def _block_shapes(mc: dict, layer: int) -> dict:
    h, w = mc["hidden_size"], jnp.bfloat16
    n, kv, d = _heads(mc)

    def mlp(width):
        return {"gate": ((h, width), w), "up": ((h, width), w),
                "down": ((width, h), w)}

    block = {"op_norm": ((h,), F32), "ffn_norm": ((h,), F32)}
    if mc["layer_types"][layer] == CONV:
        block.update({"in": ((h, 3 * h), w),
                      "conv": ((mc["conv_L_cache"], h), w),
                      "out": ((h, h), w)})
    else:
        block.update({"q": ((h, h), w), "k": ((h, kv * d), w),
                      "v": ((h, kv * d), w), "o": ((h, h), w),
                      "q_norm": ((d,), F32), "k_norm": ((d,), F32)})
    if layer < mc["num_dense_layers"]:
        block["mlp"] = mlp(mc["intermediate_size"])
    else:
        block["router"] = {"w": ((mc["num_experts"], h), F32)}
        if mc.get("use_expert_bias", True):
            block["router"]["bias"] = ((mc["num_experts"],), F32)
        block["experts"] = {f"e{e}": mlp(mc["moe_intermediate_size"])
                            for e in range(_held(mc))}
    return block


def param_shapes(mc: dict) -> dict:
    """name -> (shape, dtype), laid out as the program's checkpoint: the
    embedding's matrix is the head's too."""
    h = mc["hidden_size"]
    shapes = {"embed": ((mc["vocab_size"], h), jnp.bfloat16),
              "norm": ((h,), F32)}
    for layer in range(mc["num_hidden_layers"]):
        shapes[f"layer{layer}"] = _block_shapes(mc, layer)
    return shapes


def tenant_params(seed: int, tenant: int, model_config: dict) -> dict:
    """Tenant `tenant`'s weights in a run of `--seed seed`, on the device,
    a leaf at a time: every matrix normal with std 0.02 in bfloat16, the
    router float32 with its selection bias std 0.01, norms 1; a conv's
    `K` taps normal with std `K ** -0.5`, so that the taps' sum keeps
    its input's scale (the builder's draw: the family publishes none)."""
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
        std = {"bias": 0.01, "conv": shape[0] ** -0.5}.get(name, 0.02)
        return _normal(jax.random.fold_in(key, made[0]), shape, dtype, std)

    return build(param_shapes(model_config))


# -- the equations ------------------------------------------------------------

def _rests_in(cdt):
    """The type a taps' input rests in beside products in `cdt`."""
    return F32 if jnp.dtype(cdt) == jnp.dtype(F32) else jnp.bfloat16


def _conv_operator(p, u, mc: dict, cdt):
    """The double-gated short convolution over `u` `[n, S, hidden]`
    (normed), left-padded with zeros."""
    s_len, taps = u.shape[1], mc["conv_L_cache"]
    b, c, z = jnp.split(_ein("nsi,io->nso", u, p["in"], cdt), 3, axis=-1)
    s = (b * z).astype(_rests_in(cdt)).astype(F32)
    padded = jnp.pad(s, ((0, 0), (taps - 1, 0), (0, 0)))
    w = p["conv"].astype(F32)
    conv = sum(w[j] * padded[:, j:j + s_len] for j in range(taps))
    return _ein("nsi,io->nso", c * conv, p["out"], cdt)


def _turn(x, theta: float):
    """The rotary turn of `x` `[n, S, heads, d]` at positions 0..S-1,
    over all `d` dimensions, dimension `i` paired with `i + d / 2`."""
    s_len, d = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.outer(np.arange(s_len, dtype=np.float64), freq)
    cos = jnp.asarray(np.concatenate([np.cos(angle)] * 2, -1), F32)
    sin = jnp.asarray(np.concatenate([np.sin(angle)] * 2, -1), F32)
    swapped = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[None, :, None, :] + swapped * sin[None, :, None, :]


def _attention(p, u, mc: dict, cdt):
    """Grouped-query attention over `u` `[n, S, hidden]` (normed), each
    head of `q` and `k` normed, then turned."""
    n, s, _ = u.shape
    heads, kv, d = _heads(mc)
    eps, theta = mc["norm_eps"], mc["rope_parameters"]["rope_theta"]
    q = _ein("nsi,io->nso", u, p["q"], cdt).reshape(n, s, heads, d)
    k = _ein("nsi,io->nso", u, p["k"], cdt).reshape(n, s, kv, d)
    v = _ein("nsi,io->nso", u, p["v"], cdt).reshape(n, s, kv, d)
    q = _turn(_rms(q, p["q_norm"], eps), theta)
    k = _turn(_rms(k, p["k_norm"], eps), theta)
    # query head h reads key-value head h // (heads / kv)
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    logits = _ein("nqhd,nkhd->nhqk", q, k, cdt) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = _ein("nhqk,nkhd->nqhd", probs, v, cdt).reshape(n, s, heads * d)
    return _ein("nsi,io->nso", out, p["o"], cdt)


def routing_weights(p, h, mc):
    """`[T, num_experts]` float32: a token's weight for each routed
    expert, 0 where it did not choose it."""
    s = jax.nn.sigmoid(jnp.einsum(
        "ti,ei->te", h, p["w"], precision=jax.lax.Precision.HIGHEST))
    choice = s + p["bias"] if mc.get("use_expert_bias", True) else s
    bar = jnp.sort(choice, axis=-1)[:, -mc["num_experts_per_tok"]][:, None]
    chosen = jnp.where(choice >= bar, s, 0.0)
    if mc.get("norm_topk_prob", True):
        chosen = chosen / (chosen.sum(-1, keepdims=True) + SUM_EPS)
    return chosen * mc["routed_scaling_factor"]


def expert_layer(p, h, mc, cdt):
    """The held experts' part, for `h` `[T, hidden]`; no shared expert."""
    first = mc.get("first_expert", 0)
    weights = routing_weights(p["router"], h, mc)
    out = jnp.zeros(h.shape, F32)
    for e in range(_held(mc)):
        out = out + weights[:, first + e, None] * _mlp(
            p["experts"][f"e{e}"], h, cdt)
    return out


def _block(p, x, layer: int, mc: dict, cdt):
    eps = mc["norm_eps"]
    op = _conv_operator if mc["layer_types"][layer] == CONV else _attention
    x = x + op(p, _rms(x, p["op_norm"], eps), mc, cdt)
    h = _rms(x, p["ffn_norm"], eps)
    if "mlp" in p:
        return x + _mlp(p["mlp"], h, cdt)
    n, s, hid = h.shape
    return x + expert_layer(p, h.reshape(n * s, hid), mc, cdt).reshape(
        n, s, hid)


class _Forward:
    """The jitted pieces, a layer at a time (one compile for each layer,
    shape and precision)."""

    def __init__(self, mc: dict, cdt):
        self.mc, self.cdt = mc, cdt
        self.embed = jax.jit(lambda e, tok: e[tok].astype(F32))
        self.block = jax.jit(lambda p, x, layer: _block(p, x, layer, mc, cdt),
                             static_argnums=2)
        self.head = jax.jit(self._surprisal)

    def _surprisal(self, norm, embed, x, tokens):
        """`[n, S]`: at position i, the surprisal of token i under the
        prediction at i - 1 (position 0: 0); the head is the embedding's
        matrix."""
        logits = _ein("nsi,vi->nsv", _rms(x, norm, self.mc["norm_eps"]),
                      embed, self.cdt)
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
                params["norm"], params["embed"], x,
                jnp.asarray(padded)))[:rows.size]
    # 3. an event's score, read off the position before it
    out = surprisal[at[..., 0], every[None, :], at[..., 1]]
    out = np.where((seen >= gate) & (at[..., 1] > 0), out, 0.0)
    return np.clip(out, 0.0, SCORE_CLIP).astype(np.float32)


# -- what an event needs of the chip ----------------------------------------

FRAME_EVENTS = 512        # the step the byte count spreads the weights over


def _kinds(mc: dict) -> tuple[int, int]:
    kinds = mc["layer_types"][:mc["num_hidden_layers"]]
    return kinds.count(CONV), kinds.count(FULL)


def _matrix_params(mc: dict) -> tuple[float, float]:
    """Parameters in matrix products: (all that are held here, those one
    token's products touch: of the held experts its expected share). The
    tied embedding is counted once: it is the head's product, and a table
    of which a token reads a row."""
    h = mc["hidden_size"]
    _, kv, d = _heads(mc)
    held = _held(mc)
    chosen_here = mc["num_experts_per_tok"] * held / mc["num_experts"]
    expert, router = 3 * h * mc["moe_intermediate_size"], h * mc["num_experts"]
    operator = {CONV: 3 * h * h + h * h, FULL: 2 * h * h + 2 * h * kv * d}
    resident = touched = 0.0
    for layer in range(mc["num_hidden_layers"]):
        op = operator[mc["layer_types"][layer]]
        if layer < mc["num_dense_layers"]:
            resident += op + 3 * h * mc["intermediate_size"]
            touched += op + 3 * h * mc["intermediate_size"]
        else:
            resident += op + router + held * expert
            touched += op + router + chosen_here * expert
    head = h * mc["vocab_size"]
    return float(resident + head), float(touched + head)


def expert_leaf_bytes(mc: dict) -> int:
    """Bytes of the held experts' leaves of every expert layer: what a
    step streams once, whatever a frame routes (2 B a parameter)."""
    layers = mc["num_hidden_layers"] - mc["num_dense_layers"]
    return layers * _held(mc) * 2 * 3 * mc["hidden_size"] * mc[
        "moe_intermediate_size"]


def _mean_positions(mc: dict) -> float:
    """Positions an event of an attention layer attends to (its own
    among them), averaged over a run that goes from a seeded window to a
    full context."""
    return float((np.arange(mc["window"], mc["context_positions"]) + 1).mean())


def state_row_bytes(mc: dict) -> int:
    """A device's conv states at rest, in bytes: the taps' last `K - 1`
    inputs a conv layer, 2 B a value."""
    return _kinds(mc)[0] * 2 * (mc["conv_L_cache"] - 1) * mc["hidden_size"]


def flops_per_event(model_config: dict) -> float:
    """2 FLOPs a parameter the token's products touch (the ACTIVE
    parameters: every operator's projections, a dense MLP or the router
    and the chosen experts held here, the head over the whole
    vocabulary; a tile's padding rows are the program's business), a
    conv layer's taps (a multiply-add a tap a channel) and gates, and an
    attention layer's context: 2 x heads x head_dim for the logits and
    as much for the weighted sum, a position."""
    mc = model_config
    conv, full = _kinds(mc)
    h = mc["hidden_size"]
    return (2.0 * _matrix_params(mc)[1]
            + conv * (2.0 * mc["conv_L_cache"] + 2.0) * h
            + full * 4.0 * h * _mean_positions(mc))


def bytes_per_event(model_config: dict, score_dtype: str) -> float:
    """What any implementation must move: the held weights once a step
    of `FRAME_EVENTS` events (2 B a parameter, every held expert's leaves
    among them whatever the frame routes; the signature has no frame
    size, so the count assumes the configuration's frame of 512), plus
    the event's own state: every conv state read ONCE and written ONCE,
    an attention layer's context read once to its attended length (keys
    and values, 2 B a value) but for its own position, which is written;
    its `hn` read and written, its value in and its score out."""
    mc = model_config
    _, kv, d = _heads(mc)
    entry = 2 * 2.0 * kv * d
    return (2.0 * _matrix_params(mc)[0] / FRAME_EVENTS
            + 2.0 * state_row_bytes(mc)
            + _kinds(mc)[1] * entry * _mean_positions(mc)
            + 2 * 2.0 * mc["hidden_size"] + 8
            + jnp.dtype(score_dtype).itemsize)
