"""`ouro-stream`: the plain reference of the Ouro-2.6B streaming scorer,
written from the model's equations, and what one scored event needs of
the chip, counted from those equations.

It imports nothing of the program and takes nothing the program made:
weights come from `tenant_params(seed)`, history and frames from
benchmarks/gen.py. `model_config` is the published config.json's keys
(ByteDance/Ouro-2.6B, `model_type` `ouro`) and the scorer's own two sizes
(`window`, `context_positions`). The tokens, the statistics and the
score are the family's, and so are their few lines here: taken from
benchmarks/models/dsv3_stream.py, which states them.

What is computed: the FULL causal forward pass over each device's whole
sequence, history and every served tick, pass by pass. No cache, no
state carried between events, no grouped heads: a layer's attention in
pass `r` is one masked softmax over the keys and values that layer made
IN PASS `r` at every position of the sequence, a key-value head repeated
for the query heads that read it.

One token `x` at position `t` (`eps` = `rms_norm_eps`), U =
`total_ut_steps` passes over the L layers, the same weights in each:

    h_0 = embed(token)
    pass r = 1 .. U:  y = h_{r-1}
        layer l = 0 .. L-1:
            y = y + RMSNorm_a2(attn_{l,r}(RMSNorm_a1(y)))
            y = y + RMSNorm_m2(W_down(silu(n W_gate) * (n W_up))),
                n = RMSNorm_m1(y)
        h_r = RMSNorm_final(y)
    logits = h_U W_head
    attn_{l,r}(u): q = u Wq [heads, d], k = u Wk, v = u Wv [kv, d], no
        bias; q, k turned at t, theta = rope_theta, all d dimensions,
        pairs (i, i + d / 2); concat_h(softmax(q_h K_{h // (heads / kv)}^T
        / sqrt(d)) V_{h // (heads / kv)} over j <= t) Wo

`early_exit_threshold` 1: every token runs every pass, and the exit gate
touches no logit, so it is not computed.

A device's sequence: its last `window` stored values, then every event
it was fed; one whose sequence has reached `context_positions` starts
again from its last `window` stored values (dsv3_stream.py has the rule
in full). `run(..., compute_dtype)` rounds the two operands of every
matrix product to `compute_dtype` and accumulates in float32 (at
precision HIGHEST where that is float32); everything else is float32:
norms, softmax, residual stream, score.

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
                          # the whole vocabulary are 1.4 GB at 448 positions


# -- weights ------------------------------------------------------------------

def _heads(mc: dict) -> tuple[int, int, int]:
    """(query heads, key-value heads, a head's width)."""
    return (mc["num_attention_heads"], mc["num_key_value_heads"],
            mc["head_dim"])


def param_shapes(mc: dict) -> dict:
    """name -> (shape, dtype), laid out as the program's checkpoint: the
    stage's layers stacked `[layers, ...]` leaf by leaf, an untied head
    `[hidden, vocab]`; every leaf bfloat16, the published checkpoint's
    type."""
    h, w, n = mc["hidden_size"], jnp.bfloat16, mc["num_hidden_layers"]
    heads, kv, d = _heads(mc)
    inter = mc["intermediate_size"]
    return {"embed": ((mc["vocab_size"], h), w),
            "layers": {"attn_norm": ((n, h), w), "q": ((n, h, heads * d), w),
                       "k": ((n, h, kv * d), w), "v": ((n, h, kv * d), w),
                       "o": ((n, heads * d, h), w),
                       "attn_out_norm": ((n, h), w), "mlp_norm": ((n, h), w),
                       "gate": ((n, h, inter), w), "up": ((n, h, inter), w),
                       "down": ((n, inter, h), w),
                       "mlp_out_norm": ((n, h), w)},
            "norm": ((h,), w),
            "head": ((h, mc["vocab_size"]), w)}


def tenant_params(seed: int, tenant: int, model_config: dict) -> dict:
    """Tenant `tenant`'s weights in a run of `--seed seed`, on the device,
    a leaf at a time: every matrix normal with std 0.02, norms 1."""
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
        return _normal(jax.random.fold_in(key, made[0]), shape, dtype, 0.02)

    return build(param_shapes(model_config))


# -- the equations ------------------------------------------------------------

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
    """Causal attention over `u` `[n, S, hidden]` (normed): the keys and
    values are this layer's of this pass, at every position."""
    n, s, _ = u.shape
    heads, kv, d = _heads(mc)
    theta = mc["rope_theta"]
    q = _turn(_ein("nsi,io->nso", u, p["q"], cdt).reshape(n, s, heads, d),
              theta)
    k = _turn(_ein("nsi,io->nso", u, p["k"], cdt).reshape(n, s, kv, d), theta)
    v = _ein("nsi,io->nso", u, p["v"], cdt).reshape(n, s, kv, d)
    # query head h reads key-value head h // (heads / kv)
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    logits = _ein("nqhd,nkhd->nhqk", q, k, cdt) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = _ein("nhqk,nkhd->nqhd", probs, v, cdt).reshape(n, s, heads * d)
    return _ein("nsi,io->nso", out, p["o"], cdt)


def _block(layers, layer, x, mc: dict, cdt):
    """Layer `layer` of the stacked weights on `x`: both halves
    sandwiched between two norms."""
    p = jax.tree.map(lambda w: w[layer], layers)
    eps = mc["rms_norm_eps"]
    x = x + _rms(_attention(p, _rms(x, p["attn_norm"], eps), mc, cdt),
                 p["attn_out_norm"], eps)
    return x + _rms(_mlp(p, _rms(x, p["mlp_norm"], eps), cdt),
                    p["mlp_out_norm"], eps)


class _Forward:
    """The jitted pieces, a layer at a time (one compile for each shape
    and precision)."""

    def __init__(self, mc: dict, cdt):
        self.mc, self.cdt = mc, cdt
        self.embed = jax.jit(lambda e, tok: e[tok].astype(F32))
        self.block = jax.jit(
            lambda layers, layer, x: _block(layers, layer, x, mc, cdt))
        self.norm = jax.jit(lambda w, x: _rms(x, w, mc["rms_norm_eps"]))
        self.head = jax.jit(self._surprisal)

    def _surprisal(self, norm, head, x, tokens):
        """`[n, S]`: at position i, the surprisal of token i under the
        prediction at i - 1 (position 0: 0), from `h_U`."""
        logits = _ein("nsi,iv->nsv", _rms(x, norm, self.mc["rms_norm_eps"]),
                      head, self.cdt)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        got = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.concatenate([jnp.zeros((x.shape[0], 1), F32), -got], 1)

    def hidden(self, params, tokens):
        """`y` after the last layer of the last pass, before the final
        norm; the final norm closes every pass before it."""
        x = self.embed(params["embed"], tokens)
        for r in range(self.mc["total_ut_steps"]):
            if r:
                x = self.norm(params["norm"], x)
            for layer in range(self.mc["num_hidden_layers"]):
                x = self.block(params["layers"], layer, x)
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

FRAME_EVENTS = 16         # the step the byte count spreads the weights over


def layer_params(mc: dict) -> int:
    """Parameters of one layer: q, k, v, o, the MLP and four norms."""
    h = mc["hidden_size"]
    heads, kv, d = _heads(mc)
    return (2 * h * heads * d + 2 * h * kv * d + 3 * h * mc["intermediate_size"]
            + 4 * h)


def loop_weight_bytes(mc: dict) -> int:
    """Bytes of layer weights the passes stream a step: passes x layers x
    a layer's, 2 B a parameter."""
    return 2 * mc["total_ut_steps"] * mc["num_hidden_layers"] * layer_params(mc)


def _mean_positions(mc: dict) -> float:
    """Positions a context is attended to at (its own among them),
    averaged over a run that goes from a seeded window to a full
    context."""
    return float((np.arange(mc["window"], mc["context_positions"]) + 1).mean())


def flops_per_event(model_config: dict) -> float:
    """2 FLOPs a parameter a product touches, every layer's once a pass
    (norms are no products), the head over the whole vocabulary, and each
    (pass, layer) context: 2 x heads x head_dim for the logits and as
    much for the weighted sum, a position attended."""
    mc = model_config
    h = mc["hidden_size"]
    heads, _, d = _heads(mc)
    runs = mc["total_ut_steps"] * mc["num_hidden_layers"]
    return (2.0 * runs * (layer_params(mc) - 4 * h)
            + 2.0 * h * mc["vocab_size"]
            + runs * 4.0 * heads * d * _mean_positions(mc))


def bytes_per_event(model_config: dict, score_dtype: str) -> float:
    """What any implementation must move: every layer's weights once a
    pass and the head once, a step of `FRAME_EVENTS` events (2 B a
    parameter; the signature has no frame size, so the count assumes the
    configuration's frame of 16), the token's row of the embedding, and
    the event's own state: each (pass, layer) context read once to its
    attended length (keys and values, 2 B a value) but for its own
    position, which is written; its `hn` read and written, its value in
    and its score out."""
    mc = model_config
    h = mc["hidden_size"]
    _, kv, d = _heads(mc)
    runs = mc["total_ut_steps"] * mc["num_hidden_layers"]
    return ((loop_weight_bytes(mc) + 2.0 * h * mc["vocab_size"])
            / FRAME_EVENTS + 2.0 * h
            + runs * 2 * 2.0 * kv * d * _mean_positions(mc)
            + 2 * 2.0 * h + 8 + jnp.dtype(score_dtype).itemsize)
