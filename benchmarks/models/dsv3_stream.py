"""`dsv3-stream`: the plain reference of the DeepSeek-V3 streaming scorer,
written from the model's equations, and what one scored event needs of
the chip, counted from those equations.

It imports nothing of the program and takes nothing the program made:
weights come from `tenant_params(seed)`, history and frames from
benchmarks/gen.py. `model_config` is the published config.json's keys
(deepseek-ai/DeepSeek-V3) plus the share held here (`first_expert`,
`n_routed_experts_held`, `vocab_held`, `mtp_modules`) and the scorer's
own two sizes (`window`, `context_positions`).

What is computed: the FULL causal forward pass over each device's whole
sequence, prefill form only. No cache, no absorbed products, no grouped
products: the held experts are a loop, each over every token with its
weight (0 where the token did not choose it).

A device's sequence. Its last `window` stored values, then every event
it was fed, as tokens: `bin = clip(floor((xn + 8) / 16 * V), 0, V - 1)`,
`xn = (v - mean) / sqrt(var + 1e-6)`. The stored window is normalised by
its own mean and variance, taken value by value in stored order (`n' =
n + 1; d = v - mean; mean' = mean + d / n'; var' = var + ((v - mean') *
d - var) / n'` from mean 0, var 1, n 0: no sum, so no order of
summation to disagree about); an event by the device's running ones
BEFORE the event updates them by the same rule with `n' = min(n + 1,
window)`. The score of an event is the surprisal of its bin under the
prediction at the position before it,

    score = clip(-log softmax(RMSNorm(h_prev) W_head)[bin], 0, 50),

0 while fewer than `max(8, window // 8)` values were seen. A device
whose sequence has reached `context_positions` starts again from its
last `window` stored values (the event that filled it among them),
exactly as at the start.

One token `x` at position `p`, every layer (`eps` = `rms_norm_eps`):

    h = RMSNorm(x);  cq = RMSNorm(h W_qa);  q = cq W_qb -> heads [nope | rope]
    [c_kv | k_rope] = h W_kva;  c_kv = RMSNorm(c_kv)
    rope (YaRN) on q_rope and on the shared k_rope, at p, pairs (2i, 2i+1)
    [k_nope | v] = c_kv W_kvb per head;  k = [k_nope | k_rope]
    a = causal softmax(q k^T * (nope + rope)^-0.5 * m^2) v,
        m = 0.1 * mscale_all_dim * ln(factor) + 1
    x = x + a W_o;  h = RMSNorm(x)
    leading layers:  x = x + W_down(silu(h W_gate) * (h W_up))
    the others:      s = sigmoid(h W_g^T) (float32); choice = s + b;
        a group's score = the sum of its 2 best choices; keep the
        `topk_group` best groups; the `num_experts_per_tok` best choices
        among them; weight = chosen s / their sum * routed_scaling_factor;
        x = x + shared(h) + sum over the chosen experts HELD HERE of
        weight * expert(h): what the absent experts would add is left out

`run(..., compute_dtype)` rounds the two operands of every matrix
product to `compute_dtype` and accumulates in float32; everything else
(norms, softmax, router, residual stream, score) is float32. With
`float32` it is the plain float32 reference (precision HIGHEST); the
harness gives it the configuration's `bfloat16`; the control of
`correct` is the same one step down (`float8_e4m3fn`: each float8 value
is exact in bfloat16 and a product of two is exact in float32, so the
products run in bfloat16 with float32 accumulation).

Multi-token prediction (`forecast_bins`, the query path's reference):
`h' = W_eh [RMSNorm(h_t) ; RMSNorm(Emb(x_{t+1}))]` over the window, the
main head's most likely next bin standing for the token after the last;
one block of the expert kind; the module's own norm; the shared head.

It runs devices in blocks of `BLOCK_ROWS` sequences and a layer at a
time, so it fits beside the weights once the runtime has stopped.
"""

from __future__ import annotations

import functools
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

SCORE_CLIP = 50.0
EPS = 1e-6
BLOCK_ROWS = 32           # sequences forwarded at once (4,608 tokens at 144)
F32 = jnp.float32


# -- weights ------------------------------------------------------------------

def _held(mc: dict) -> tuple[int, int]:
    return (mc.get("n_routed_experts_held") or mc["n_routed_experts"],
            mc.get("vocab_held") or mc["vocab_size"])


def _block_shapes(mc: dict, moe: bool) -> dict:
    h, nh = mc["hidden_size"], mc["num_attention_heads"]
    nope, rope = mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
    w, f = jnp.bfloat16, F32

    def mlp(width):
        return {"gate": ((h, width), w), "up": ((h, width), w),
                "down": ((width, h), w)}

    block = {"attn_norm": ((h,), f), "mlp_norm": ((h,), f),
             "q_a": ((h, mc["q_lora_rank"]), w),
             "q_a_norm": ((mc["q_lora_rank"],), f),
             "q_b": ((mc["q_lora_rank"], nh * (nope + rope)), w),
             "kv_a": ((h, mc["kv_lora_rank"] + rope), w),
             "kv_a_norm": ((mc["kv_lora_rank"],), f),
             "kv_b": ((mc["kv_lora_rank"], nh * (nope + mc["v_head_dim"])), w),
             "o": ((nh * mc["v_head_dim"], h), w)}
    if moe:
        e = mc["n_routed_experts"]
        block["router"] = {"w": ((e, h), f), "bias": ((e,), f)}
        block["shared"] = mlp(mc["moe_intermediate_size"])
        block["experts"] = {f"e{e}": mlp(mc["moe_intermediate_size"])
                            for e in range(_held(mc)[0])}
    else:
        block["mlp"] = mlp(mc["intermediate_size"])
    return block


def param_shapes(mc: dict) -> dict:
    """name -> (shape, dtype), laid out as the program's checkpoint."""
    h, vocab = mc["hidden_size"], _held(mc)[1]
    shapes = {"embed": ((vocab, h), jnp.bfloat16), "norm": ((h,), F32),
              "head": ((h, vocab), jnp.bfloat16)}
    for layer in range(mc["num_hidden_layers"]):
        shapes[f"layer{layer}"] = _block_shapes(
            mc, layer >= mc["first_k_dense_replace"])
    for m in range(mc.get("mtp_modules", 0)):
        shapes[f"mtp{m}"] = {"hnorm": ((h,), F32), "enorm": ((h,), F32),
                             "norm": ((h,), F32),
                             "eh_proj": ((2 * h, h), jnp.bfloat16),
                             "block": _block_shapes(mc, True)}
    return shapes


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def tenant_params(seed: int, tenant: int, model_config: dict) -> dict:
    """Tenant `tenant`'s weights in a run of `--seed seed`, on the device,
    a leaf at a time: normal with std 0.02 (the router's bias 0.01),
    norms 1; matrices bfloat16, norms and the router float32."""
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
        return _normal(jax.random.fold_in(key, made[0]), shape, dtype,
                       0.01 if name == "bias" else 0.02)

    return build(param_shapes(model_config))


# -- the equations ------------------------------------------------------------

def _operands(cdt):
    """How a product's operands are rounded: to `cdt`, then held in a type
    the chip multiplies (a float8 value is exact in bfloat16)."""
    cdt = jnp.dtype(cdt)
    if cdt.itemsize == 1:
        return lambda a: a.astype(cdt).astype(jnp.bfloat16), None
    if cdt == jnp.dtype(F32):
        return lambda a: a.astype(F32), jax.lax.Precision.HIGHEST
    return lambda a: a.astype(cdt), None


def _ein(spec, a, b, cdt):
    rnd, precision = _operands(cdt)
    return jnp.einsum(spec, rnd(a), rnd(b), preferred_element_type=F32,
                      precision=precision)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope_tables(mc: dict, positions: int):
    rs, dim, base = mc.get("rope_scaling"), mc["qk_rope_head_dim"], mc[
        "rope_theta"]
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rs:
        orig = rs["original_max_position_embeddings"]

        def correction_dim(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
        span = (high - low) if high != low else 0.001
        keep = 1.0 - np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
        freq = freq / rs["factor"] * (1.0 - keep) + freq * keep
    angle = np.outer(np.arange(positions, dtype=np.float64), freq)
    return (jnp.asarray(np.cos(angle), F32), jnp.asarray(np.sin(angle), F32))


def _rotate(x, cos, sin):
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def _softmax_scale(mc: dict) -> float:
    scale = (mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"]) ** -0.5
    rs = mc.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


def _mlp(p, h, cdt):
    return _ein("...i,io->...o",
                jax.nn.silu(_ein("...i,io->...o", h, p["gate"], cdt))
                * _ein("...i,io->...o", h, p["up"], cdt), p["down"], cdt)


def _attention(p, h, mc, cdt):
    """MLA, prefill form, over `h` `[n, S, hidden]` (normed)."""
    nh, nope = mc["num_attention_heads"], mc["qk_nope_head_dim"]
    lora, eps = mc["kv_lora_rank"], mc["rms_norm_eps"]
    n, s, _ = h.shape
    cos, sin = _rope_tables(mc, s)
    cq = _rms(_ein("nsi,io->nso", h, p["q_a"], cdt), p["q_a_norm"], eps)
    q = _ein("nsi,io->nso", cq, p["q_b"], cdt).reshape(n, s, nh, -1)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = _rotate(q_rope, cos[None, :, None, :], sin[None, :, None, :])
    kv = _ein("nsi,io->nso", h, p["kv_a"], cdt)
    c_kv = _rms(kv[..., :lora], p["kv_a_norm"], eps)
    k_rope = _rotate(kv[..., lora:], cos[None], sin[None])
    up = _ein("nsc,co->nso", c_kv, p["kv_b"], cdt).reshape(n, s, nh, -1)
    k_nope, v = up[..., :nope], up[..., nope:]
    logits = (_ein("nqhd,nkhd->nhqk", q_nope, k_nope, cdt)
              + _ein("nqhd,nkd->nhqk", q_rope, k_rope, cdt)
              ) * _softmax_scale(mc)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = _ein("nhqk,nkhd->nqhd", probs, v, cdt).reshape(n, s, -1)
    return _ein("nsi,io->nso", out, p["o"], cdt)


def routing_weights(p, h, mc):
    """`[T, n_routed_experts]` float32: a token's weight for each routed
    expert, 0 where it did not choose it."""
    s = jax.nn.sigmoid(jnp.einsum(
        "ti,ei->te", h, p["w"], precision=jax.lax.Precision.HIGHEST))
    choice = s + p["bias"]
    t, e = choice.shape
    groups = choice.reshape(t, mc["n_group"], -1)
    group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
    bar = jnp.sort(group_score, axis=-1)[:, -mc["topk_group"]][:, None]
    kept = jnp.repeat(group_score >= bar, e // mc["n_group"], axis=1)
    choice = jnp.where(kept, choice, -jnp.inf)
    bar = jnp.sort(choice, axis=-1)[:, -mc["num_experts_per_tok"]][:, None]
    chosen = jnp.where(choice >= bar, s, 0.0)
    return chosen / chosen.sum(-1, keepdims=True) * mc["routed_scaling_factor"]


def expert_layer(p, h, mc, cdt):
    """Shared expert plus the held experts' part, for `h` `[T, hidden]`."""
    first, (held, _) = mc.get("first_expert", 0), _held(mc)
    weights = routing_weights(p["router"], h, mc)
    out = _mlp(p["shared"], h, cdt)
    for e in range(held):
        out = out + weights[:, first + e, None] * _mlp(
            p["experts"][f"e{e}"], h, cdt)
    return out


def _block(p, x, mc, cdt):
    eps = mc["rms_norm_eps"]
    x = x + _attention(p, _rms(x, p["attn_norm"], eps), mc, cdt)
    h = _rms(x, p["mlp_norm"], eps)
    if "mlp" in p:
        return x + _mlp(p["mlp"], h, cdt)
    n, s, hid = h.shape
    return x + expert_layer(p, h.reshape(n * s, hid), mc, cdt).reshape(
        n, s, hid)


class _Forward:
    """The jitted pieces, a layer at a time (one compile for each kind of
    layer, shape and precision)."""

    def __init__(self, mc: dict, cdt):
        self.mc, self.cdt = mc, cdt
        self.embed = jax.jit(lambda e, tok: e[tok].astype(F32))
        self.block = jax.jit(lambda p, x: _block(p, x, mc, cdt))
        self.head = jax.jit(self._surprisal)
        self.logits = jax.jit(lambda norm, head, h: _ein(
            "ni,io->no", _rms(h, norm, mc["rms_norm_eps"]), head, cdt))

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
            x = self.block(params[f"layer{layer}"], x)
        return x


# -- tokens -------------------------------------------------------------------

def _bin(xn, vocab: int):
    return jnp.clip(jnp.floor((xn + 8.0) / 16.0 * vocab), 0,
                    vocab - 1).astype(jnp.int32)


def _welford(mean, var, n, v, cap):
    n1 = jnp.minimum(n + 1, cap)
    d = v - mean
    mean1 = mean + d / n1
    var1 = var + ((v - mean1) * d - var) / n1
    return mean1, var1, n1


@functools.partial(jax.jit, static_argnames=("vocab",))
def _window_tokens(values, vocab: int):
    """A stored window `[D, W]`: its tokens and its (mean, var)."""
    d, w = values.shape

    def take(carry, v):
        return _welford(*carry, v, w), None

    (mean, var, _), _ = jax.lax.scan(
        take, (jnp.zeros(d, F32), jnp.ones(d, F32), jnp.zeros(d, jnp.int32)),
        values.T)
    xn = (values - mean[:, None]) / jnp.sqrt(var + EPS)[:, None]
    return _bin(xn, vocab), mean, jnp.maximum(var, EPS)


@functools.partial(jax.jit, static_argnames=("vocab", "window"))
def _event_tokens(mean, var, n, v, given, vocab: int, window: int):
    """One event a device: its token under the statistics before it, the
    count before it, and the statistics after (kept where not `given`)."""
    token = _bin((v - mean) / jnp.sqrt(var + EPS), vocab)
    mean1, var1, n1 = _welford(mean, var, n, v, window)
    return token, n, (jnp.where(given, mean1, mean),
                      jnp.where(given, var1, var), jnp.where(given, n1, n))


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


def forecast_bins(params, window_values: np.ndarray, model_config: dict,
                  compute_dtype: str):
    """(draft [D], log-probabilities of the bin after it [D, V]) from
    stored windows [D, W]: the query path's reference."""
    mc = model_config
    eps, vocab = mc["rms_norm_eps"], _held(mc)[1]
    fwd = _Forward(mc, compute_dtype)
    tokens, _, _ = _window_tokens(jnp.asarray(window_values, F32),
                                  vocab=vocab)
    h = fwd.hidden(params, tokens)
    logits = fwd.logits(params["norm"], params["head"], h[:, -1])
    draft = jnp.argmax(logits, -1).astype(jnp.int32)
    if not mc.get("mtp_modules"):
        return np.asarray(draft), np.asarray(jax.nn.log_softmax(logits, -1))
    p = params["mtp0"]
    following = jnp.concatenate([tokens[:, 1:], draft[:, None]], 1)
    joined = jnp.concatenate([
        _rms(h, p["hnorm"], eps),
        _rms(fwd.embed(params["embed"], following), p["enorm"], eps)], -1)
    h2 = fwd.block(p["block"], _ein("nsi,io->nso", joined, p["eh_proj"],
                                    compute_dtype))
    after = fwd.logits(p["norm"], params["head"], h2[:, -1])
    return np.asarray(draft), np.asarray(jax.nn.log_softmax(after, -1))


# -- what an event needs of the chip ----------------------------------------

FRAME_EVENTS = 1024       # the step the byte count spreads the weights over


def _matrix_params(mc: dict) -> tuple[float, float]:
    """Parameters in matrix products: (all that are held here, those one
    token's products touch: of the held experts its expected share)."""
    h, nh = mc["hidden_size"], mc["num_attention_heads"]
    nope, rope = mc["qk_nope_head_dim"], mc["qk_rope_head_dim"]
    attention = (h * mc["q_lora_rank"] + mc["q_lora_rank"] * nh * (nope + rope)
                 + h * (mc["kv_lora_rank"] + rope)
                 + mc["kv_lora_rank"] * nh * (nope + mc["v_head_dim"])
                 + nh * mc["v_head_dim"] * h)
    dense = 3 * h * mc["intermediate_size"]
    expert = 3 * h * mc["moe_intermediate_size"]
    router = h * mc["n_routed_experts"]
    held, vocab = _held(mc)
    chosen_here = mc["num_experts_per_tok"] * held / mc["n_routed_experts"]
    n_dense = mc["first_k_dense_replace"]
    n_moe = mc["num_hidden_layers"] - n_dense
    head = h * vocab
    resident = (n_dense * (attention + dense)
                + n_moe * (attention + router + (1 + held) * expert)
                + 2 * head)                      # embedding and head
    touched = (n_dense * (attention + dense)
               + n_moe * (attention + router + (1 + chosen_here) * expert)
               + head)
    return float(resident), float(touched)


def _mean_context(mc: dict) -> float:
    """Positions an event attends to, averaged over a run that goes from
    a seeded window to a full context."""
    return (mc["window"] + mc["context_positions"]) / 2.0


def flops_per_event(model_config: dict) -> float:
    """2 FLOPs a parameter the token's products touch (attention, dense
    MLP or router + shared expert + the chosen experts held here, the
    head over the held vocabulary), plus attention over the context:
    2 x heads x ((kv_lora + rope) for the logits + kv_lora for the
    weighted sum) a position a layer."""
    mc = model_config
    attend = 2.0 * mc["num_attention_heads"] * (
        2 * mc["kv_lora_rank"] + mc["qk_rope_head_dim"])
    return (2.0 * _matrix_params(mc)[1]
            + mc["num_hidden_layers"] * attend * _mean_context(mc))


def bytes_per_event(model_config: dict, score_dtype: str) -> float:
    """The held weights once a step of `FRAME_EVENTS` events (2 B a
    parameter; the signature has no frame size, so the count assumes the
    configuration's frame of 1,024), plus the event's own context read
    once and one position of it written (2 B a value), its `hn` read and
    written, its value in and its score out."""
    mc = model_config
    entry = 2.0 * (mc["kv_lora_rank"] + mc["qk_rope_head_dim"])
    context = mc["num_hidden_layers"] * entry * (_mean_context(mc) + 1)
    return (2.0 * _matrix_params(mc)[0] / FRAME_EVENTS + context
            + 2 * 2.0 * mc["hidden_size"] + 8
            + jnp.dtype(score_dtype).itemsize)
