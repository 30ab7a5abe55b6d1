"""`laguna-stream`: the plain reference of the Laguna-S-2.1 streaming
scorer, written from the model's equations, and what one scored event
needs of the chip, counted from those equations.

It imports nothing of the program and takes nothing the program made:
weights come from `tenant_params(seed)`, history and frames from
benchmarks/gen.py. `model_config` is the published config.json's keys
(poolside/Laguna-S-2.1) plus the share held here (`first_expert`,
`num_experts_held`, `vocab_held`) and the scorer's own two sizes
(`window`, `context_positions`). The tokens, the statistics and the
score are the family's, and so are their few lines here: taken from
benchmarks/models/dsv3_stream.py, which states them.

What is computed: the FULL causal forward pass over each device's whole
sequence. No cache, no wrapped window, no grouped heads, no grouped
products: a key-value head is repeated for the query heads that read
it, a sliding layer is the same attention under a banded mask, the held
experts are a loop, each over every token with its weight (0 where the
token did not choose it).

One token `x` at position `t`, layer `l` with `n = num_attention_heads_
per_layer[l]` heads of `d = head_dim` over `kv = num_key_value_heads`:

    u = RMSNorm(x);  q = u Wq [n, d];  k = u Wk, v = u Wv [kv, d]
    rope on q and k at t, pairs (2i, 2i+1): a full layer by
        rope_parameters.full_attention (YaRN frequencies over the first
        partial_rotary_factor * d dimensions, the others untouched, cos
        and sin times attention_factor), a sliding layer by plain rope
        (theta of rope_parameters.sliding_attention) over all d
    a_h = softmax(q_h K_{h // (n / kv)}^T / sqrt(d)) V_{h // (n / kv)}
        over j <= t (full) or t - sliding_window < j <= t (sliding)
    x = x + concat_h(sigmoid(u Wg)_h * a_h) Wo;  u2 = RMSNorm(x)
    dense layers:  x = x + W_down(silu(u2 W_gate) * (u2 W_up))
    the others:    p = softmax(u2 Wr^T) (float32) over num_experts; the
        num_experts_per_tok largest kept; weight = kept p / their sum *
        moe_routed_scaling_factor; x = x + shared(u2) + sum over the
        kept experts HELD HERE of weight * expert(u2): what the absent
        experts would add is left out

A device's sequence: its last `window` stored values, then every event
it was fed; one whose sequence has reached `context_positions` starts
again from its last `window` stored values (dsv3_stream.py has the
rule in full). `run(..., compute_dtype)` rounds the two operands of
every matrix product to `compute_dtype` and accumulates in float32;
everything else (norms, softmaxes, router, gate, residual stream,
score) is float32.

It runs devices in blocks of `BLOCK_ROWS` sequences and a layer at a
time, so it fits beside the weights once the runtime has stopped.
"""

from __future__ import annotations

import gc
import math

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
    _rotate,
    _window_tokens,
)

BLOCK_ROWS = 4            # sequences forwarded at once (3,008 tokens at 752)


# -- weights ------------------------------------------------------------------

def _held(mc: dict) -> tuple[int, int]:
    return (mc.get("num_experts_held") or mc["num_experts"],
            mc.get("vocab_held") or mc["vocab_size"])


def _block_shapes(mc: dict, layer: int) -> dict:
    h, d = mc["hidden_size"], mc["head_dim"]
    n = mc["num_attention_heads_per_layer"][layer]
    kv = mc["num_key_value_heads"] * d
    w = jnp.bfloat16

    def mlp(width):
        return {"gate": ((h, width), w), "up": ((h, width), w),
                "down": ((width, h), w)}

    block = {"attn_norm": ((h,), F32), "mlp_norm": ((h,), F32),
             "q": ((h, n * d), w), "k": ((h, kv), w), "v": ((h, kv), w),
             "head_gate": ((h, n), w), "o": ((n * d, h), w)}
    if mc["mlp_layer_types"][layer] == "dense":
        block["mlp"] = mlp(mc["intermediate_size"])
    else:
        block["router"] = {"w": ((mc["num_experts"], h), F32)}
        block["shared"] = mlp(mc["shared_expert_intermediate_size"])
        block["experts"] = {f"e{e}": mlp(mc["moe_intermediate_size"])
                            for e in range(_held(mc)[0])}
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
    a leaf at a time: normal with std 0.02, norms 1; matrices bfloat16,
    norms and the router float32."""
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

def _rope_tables(mc: dict, kind: str, positions: int):
    """(cos, sin) `[positions, rotated / 2]` of a kind of layer, the
    attention factor in them, and the rotated width."""
    rp = mc["rope_parameters"][kind]
    dim = int(mc["head_dim"] * rp.get("partial_rotary_factor", 1))
    base = rp["rope_theta"]
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type") == "yarn":
        orig = rp["original_max_position_embeddings"]

        def correction_dim(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
        span = (high - low) if high != low else 0.001
        keep = 1.0 - np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
        freq = freq / rp["factor"] * (1.0 - keep) + freq * keep
    angle = np.outer(np.arange(positions, dtype=np.float64), freq)
    factor = np.float32(rp.get("attention_factor") or 1.0)
    return (jnp.asarray(np.cos(angle), F32) * factor,
            jnp.asarray(np.sin(angle), F32) * factor, dim)


def _attention(p, u, layer: int, mc: dict, cdt):
    """Gated grouped-query attention over `u` `[n, S, hidden]` (normed)."""
    n, s, _ = u.shape
    heads = mc["num_attention_heads_per_layer"][layer]
    kv, d = mc["num_key_value_heads"], mc["head_dim"]
    kind = mc["layer_types"][layer]
    cos, sin, dim = _rope_tables(mc, kind, s)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]

    def turned(x):
        return jnp.concatenate(
            [_rotate(x[..., :dim], cos, sin), x[..., dim:]], -1)

    q = turned(_ein("nsi,io->nso", u, p["q"], cdt).reshape(n, s, heads, d))
    k = turned(_ein("nsi,io->nso", u, p["k"], cdt).reshape(n, s, kv, d))
    v = _ein("nsi,io->nso", u, p["v"], cdt).reshape(n, s, kv, d)
    # query head h reads key-value head h // (heads / kv)
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    logits = _ein("nqhd,nkhd->nhqk", q, k, cdt) / math.sqrt(d)
    at = jnp.arange(s)
    seen = at[None, :] <= at[:, None]
    if kind == "sliding_attention":
        seen &= at[:, None] - at[None, :] < mc["sliding_window"]
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    out = _ein("nhqk,nkhd->nqhd", probs, v, cdt)
    gate = jax.nn.sigmoid(_ein("nsi,ih->nsh", u, p["head_gate"], cdt))
    return _ein("nsi,io->nso", (out * gate[..., None]).reshape(n, s, -1),
                p["o"], cdt)


def routing_weights(p, h, mc):
    """`[T, num_experts]` float32: a token's weight for each routed
    expert, 0 where it did not choose it."""
    probs = jax.nn.softmax(jnp.einsum(
        "ti,ei->te", h, p["w"], precision=jax.lax.Precision.HIGHEST), -1)
    bar = jnp.sort(probs, axis=-1)[:, -mc["num_experts_per_tok"]][:, None]
    chosen = jnp.where(probs >= bar, probs, 0.0)
    return (chosen / chosen.sum(-1, keepdims=True)
            * mc["moe_routed_scaling_factor"])


def expert_layer(p, h, mc, cdt):
    """Shared expert plus the held experts' part, for `h` `[T, hidden]`."""
    first, (held, _) = mc.get("first_expert", 0), _held(mc)
    weights = routing_weights(p["router"], h, mc)
    out = _mlp(p["shared"], h, cdt)
    for e in range(held):
        out = out + weights[:, first + e, None] * _mlp(
            p["experts"][f"e{e}"], h, cdt)
    return out


def _block(p, x, layer: int, mc: dict, cdt):
    eps = mc["rms_norm_eps"]
    x = x + _attention(p, _rms(x, p["attn_norm"], eps), layer, mc, cdt)
    h = _rms(x, p["mlp_norm"], eps)
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


# -- what an event needs of the chip ----------------------------------------

FRAME_EVENTS = 256        # the step the byte count spreads the weights over


def _matrix_params(mc: dict) -> tuple[float, float]:
    """Parameters in matrix products: (all that are held here, those one
    token's products touch: of the held experts its expected share)."""
    h, d = mc["hidden_size"], mc["head_dim"]
    kv = mc["num_key_value_heads"] * d
    held, vocab = _held(mc)
    chosen_here = mc["num_experts_per_tok"] * held / mc["num_experts"]
    expert = 3 * h * mc["moe_intermediate_size"]
    shared = 3 * h * mc["shared_expert_intermediate_size"]
    resident = touched = 0.0
    for layer in range(mc["num_hidden_layers"]):
        n = mc["num_attention_heads_per_layer"][layer]
        attention = 2 * h * n * d + 2 * h * kv + h * n
        if mc["mlp_layer_types"][layer] == "dense":
            resident += attention + 3 * h * mc["intermediate_size"]
            touched += attention + 3 * h * mc["intermediate_size"]
        else:
            router = h * mc["num_experts"]
            resident += attention + router + shared + held * expert
            touched += attention + router + shared + chosen_here * expert
    head = h * vocab
    return float(resident + 2 * head), float(touched + head)


def _mean_positions(mc: dict, layer: int) -> float:
    """Positions an event of layer `layer` attends to (its own among
    them), averaged over a run that goes from a seeded window to a full
    context."""
    reach = np.arange(mc["window"], mc["context_positions"]) + 1
    if mc["layer_types"][layer] == "sliding_attention":
        reach = np.minimum(reach, mc["sliding_window"])
    return float(reach.mean())


def flops_per_event(model_config: dict) -> float:
    """2 FLOPs a parameter the token's products touch (attention's
    projections and gate, dense MLP or router + shared expert + the
    chosen experts held here, the head over the held vocabulary), plus
    attention over the context: 2 x heads x head_dim for the logits and
    as much for the weighted sum, a position a layer."""
    mc = model_config
    attend = sum(4.0 * mc["num_attention_heads_per_layer"][layer]
                 * mc["head_dim"] * _mean_positions(mc, layer)
                 for layer in range(mc["num_hidden_layers"]))
    return 2.0 * _matrix_params(mc)[1] + attend


def bytes_per_event(model_config: dict, score_dtype: str) -> float:
    """What any implementation must move: the held weights once a step
    of `FRAME_EVENTS` events (2 B a parameter; the signature has no frame
    size, so the count assumes the configuration's frame of 256), plus
    the event's own contexts read once (keys and values, 2 B a value, a
    sliding layer's as far as its window reaches) and one position of
    each written, its `hn` read and written, its value in and its score
    out."""
    mc = model_config
    entry = 2 * 2.0 * mc["num_key_value_heads"] * mc["head_dim"]
    context = sum(entry * _mean_positions(mc, layer)
                  for layer in range(mc["num_hidden_layers"]))
    return (2.0 * _matrix_params(mc)[0] / FRAME_EVENTS + context
            + 2 * 2.0 * mc["hidden_size"] + 8
            + jnp.dtype(score_dtype).itemsize)
