"""`lstm-stream`: the plain reference of the streaming LSTM anomaly scorer,
written from the model's equations, and what one scored event needs of
the chip, computed from the shapes.

It imports nothing of the program and takes nothing the program made:
weights come from `init_params(seed)`, history and frames from
benchmarks/gen.py. The harness installs the same weights into the
program (a checkpoint roll-out, `swap_model_params`) and sends it the
same history and frames.

The model (per device, one event at a time; `W` = window, all state
float32, matrix products in `compute_dtype` with the product rounded to
that type, as the configuration states):

    seed from the last W history values x:  mean, var over the window;
        run the cell over (x - mean) / sqrt(var + 1e-6); pred = head(h)
    no history (a cold fleet): mean, pred, h, c = 0; var = 1; n = 0
    event v:
        score = clip(|(v - mean) / sqrt(var + 1e-6) - pred|, 0, 50)
                (0 while fewer than max(8, W // 8) values were seen)
        n' = min(n + 1, W); d = v - mean; mean' = mean + d / n'
        var' = var + ((v - mean') * d - var) / n'
        x = (v - mean') / sqrt(var' + 1e-6)
        gates = x @ wx + h @ wh + b; i, f, g, o = split(gates)
        c' = sigmoid(f) * c + sigmoid(i) * tanh(g); h' = sigmoid(o) * tanh(c')
        pred' = h' @ w_head + b_head

The control for `correct` is this same reference with `compute_dtype`
one step below the configuration's (benchmarks/compare.py, `LOWER`).

The counts are a copy of the arithmetic in `models/lstm.py`
(`flops_per_event`) and of the state layout in
`StreamingLstmModel.init_state`, kept here so that a later change to the
model file cannot move the yardstick. Only work the algorithm needs is
counted: a padded row of a dispatch counts nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SCORE_CLIP = 50.0
EPS = 1e-6
BLOCK_EVENTS = 1 << 22      # events the reference holds on the device at once
SEED_ROWS = 1 << 16         # devices it seeds in one call: the scan keeps
                            # every step's output, 16 KB a device in float32


@functools.partial(jax.jit, static_argnames=("hidden", "layers"))
def _make_params(key, hidden: int, layers: int) -> dict:
    keys = jax.random.split(key, 2 * layers + 1)
    params, d_in = {}, 1
    for layer in range(layers):
        params[f"lstm{layer}"] = {
            "wx": jax.random.normal(keys[2 * layer], (d_in, 4 * hidden),
                                    jnp.float32) / np.sqrt(d_in),
            "wh": jax.random.normal(keys[2 * layer + 1], (hidden, 4 * hidden),
                                    jnp.float32) / np.sqrt(hidden),
            # forget-gate bias +1
            "b": jnp.concatenate([jnp.zeros(hidden), jnp.ones(hidden),
                                  jnp.zeros(2 * hidden)]).astype(jnp.float32)}
        d_in = hidden
    params["head"] = {
        "w": jax.random.normal(keys[-1], (hidden, 1), jnp.float32)
        / np.sqrt(hidden),
        "b": jnp.zeros((1,), jnp.float32)}
    return params


def init_params(seed: int, hidden: int, layers: int = 1) -> dict:
    """The weights, on the device, in one jitted call from the seed, laid
    out as the program's checkpoint format names them."""
    return _make_params(jax.random.PRNGKey(int(seed) % (2 ** 32)),
                        hidden=hidden, layers=layers)


def tenant_params(seed: int, tenant: int, model_config: dict) -> dict:
    """Tenant `tenant`'s weights in a run of `--seed seed`: one set a
    tenant, the same in the program (which is handed them) and here."""
    return init_params(int(seed) % (2 ** 32) + tenant, model_config["hidden"],
                       model_config.get("layers", 1))


def _matmul(x, w, cdt):
    """Product in `cdt`, rounded to `cdt`, read back as float32. A type
    the chip has no product for (float8) is rounded to, then multiplied
    exactly: the rounding of the operands is what the control tests."""
    cdt = jnp.dtype(cdt)
    if cdt.itemsize == 1:
        xq = x.astype(cdt).astype(jnp.float32)
        wq = w.astype(cdt).astype(jnp.float32)
        out = jnp.matmul(xq, wq, precision=jax.lax.Precision.HIGHEST)
        return out.astype(cdt).astype(jnp.float32)
    return (x.astype(cdt) @ w.astype(cdt)).astype(jnp.float32)


def _cell(p, x, h, c, cdt):
    gates = _matmul(x, p["wx"], cdt) + _matmul(h, p["wh"], cdt) + p["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


def _layers(params) -> int:
    return sum(1 for k in params if k.startswith("lstm"))


def seed_state(params, hist, cdt):
    """State after the last W history values. hist: [D, W] float32,
    oldest first."""
    d, w = hist.shape
    mean = hist.sum(-1) / w
    var = ((hist - mean[:, None]) ** 2).sum(-1) / w
    xn = (hist - mean[:, None]) / jnp.sqrt(var + EPS)[:, None]
    seq = jnp.swapaxes(xn[:, :, None], 0, 1)            # [W, D, 1]
    state = {}
    for layer in range(_layers(params)):
        p = params[f"lstm{layer}"]
        hidden = p["wh"].shape[0]

        def step(carry, x_t, p=p):
            h, c = _cell(p, x_t, carry[0], carry[1], cdt)
            return (h, c), h

        zero = jnp.zeros((d, hidden), jnp.float32)
        (h, c), hs = jax.lax.scan(step, (zero, zero), seq)
        state[f"h{layer}"], state[f"c{layer}"] = h, c
        # between layers, and into the head, outputs travel in `cdt`
        seq = hs.astype(cdt).astype(jnp.float32)
    head = params["head"]
    state["pred"] = (seq[-1] @ head["w"] + head["b"])[:, 0]
    state["mean"] = mean
    state["var"] = jnp.maximum(var, EPS)
    state["count"] = jnp.full((d,), w, jnp.int32)
    return state


def cold_state(params, devices: int) -> dict:
    """State of a fleet that has reported nothing yet."""
    zero = jnp.zeros((devices,), jnp.float32)
    state = {"pred": zero, "mean": zero, "var": jnp.ones_like(zero),
             "count": jnp.zeros((devices,), jnp.int32)}
    for layer in range(_layers(params)):
        hidden = params[f"lstm{layer}"]["wh"].shape[0]
        state[f"h{layer}"] = jnp.zeros((devices, hidden), jnp.float32)
        state[f"c{layer}"] = jnp.zeros((devices, hidden), jnp.float32)
    return state


def step_score(params, state, v, window: int, cdt):
    """One event for every device: (scores [D], next state)."""
    mean, var, n = state["mean"], state["var"], state["count"]
    xn = (v - mean) / jnp.sqrt(var + EPS)
    enough = n >= max(8, window // 8)
    score = jnp.clip(jnp.where(enough, jnp.abs(xn - state["pred"]), 0.0),
                     0.0, SCORE_CLIP)
    n1 = jnp.minimum(n + 1, window)
    d = v - mean
    mean1 = mean + d / n1
    var1 = var + ((v - mean1) * d - var) / n1
    x = ((v - mean1) / jnp.sqrt(var1 + EPS))[:, None]
    out = {"mean": mean1, "var": var1, "count": n1}
    for layer in range(_layers(params)):
        h, c = _cell(params[f"lstm{layer}"], x, state[f"h{layer}"],
                     state[f"c{layer}"], cdt)
        out[f"h{layer}"], out[f"c{layer}"] = h, c
        x = h
    head = params["head"]
    out["pred"] = (x @ head["w"] + head["b"])[:, 0]
    return score, out


def run(params, hist: np.ndarray, frames: np.ndarray, fed: np.ndarray,
        model_config: dict, compute_dtype: str,
        block: int | None = None) -> np.ndarray:
    """Scores [T, D] float32 for ticks [T, D] (one event a device a tick,
    in order) after seeding from hist [D, >=W], or from nothing where
    hist is [D, 0]. `fed` [T, D] says which
    events the program was given: a device keeps its state through a
    tick it was not fed. Seeds in blocks of `SEED_ROWS` devices and runs
    in blocks of `block` ticks so that what it holds on the device stays
    small."""
    cdt = jnp.dtype(compute_dtype)
    window = int(model_config["window"])
    ticks, devices = frames.shape
    block = block or max(1, min(256, BLOCK_EVENTS // devices))

    @jax.jit
    def seed(params, h):
        return seed_state(params, h, cdt)

    @jax.jit
    def advance(params, state, vs, given):
        def body(st, x):
            v, on = x
            score, new = step_score(params, st, v, window, cdt)
            keep = jax.tree.map(
                lambda a, b: jnp.where(on.reshape((-1,) + (1,) * (a.ndim - 1)),
                                       a, b), new, st)
            return keep, score

        return jax.lax.scan(body, state, (vs, given))

    if hist.shape[1] == 0:
        state = cold_state(params, devices)
    else:
        parts = [seed(params, jnp.asarray(hist[lo:lo + SEED_ROWS, -window:],
                                          jnp.float32))
                 for lo in range(0, devices, SEED_ROWS)]
        state = jax.tree.map(lambda *rows: jnp.concatenate(rows), *parts)
    out = np.empty(frames.shape, np.float32)
    for lo in range(0, ticks, block):
        n = min(block, ticks - lo)
        vs = np.zeros((block, devices), np.float32)      # one compiled shape
        given = np.zeros((block, devices), bool)
        vs[:n], given[:n] = frames[lo:lo + n], fed[lo:lo + n]
        state, scores = advance(params, state, jnp.asarray(vs),
                                jnp.asarray(given))
        out[lo:lo + n] = np.asarray(scores)[:n]
    return out


# -- what an event needs of the chip ----------------------------------------

_SCORE_BYTES = {"float16": 2, "bfloat16": 2, "float32": 4}


def flops_per_event(model_config: dict) -> float:
    """One cell step an event: four gates, 2 FLOPs a multiply-add over
    (input + hidden) columns, plus the head's projection."""
    hidden = model_config["hidden"]
    flops, d_in = 0.0, 1
    for _ in range(model_config.get("layers", 1)):
        flops += 8.0 * hidden * (d_in + hidden)
        d_in = hidden
    return flops + 2.0 * hidden


def state_row_bytes(model_config: dict) -> int:
    """One device's streaming state: pred, mean, var (float32), count
    (int32), and h, c of `hidden` float32 a layer."""
    return (4 * 4 + model_config.get("layers", 1) * 2
            * model_config["hidden"] * 4)


def bytes_per_event(model_config: dict, score_dtype: str) -> float:
    """HBM traffic one event needs: its state row read and written, its
    (device id, value) in, its score out."""
    return (2 * state_row_bytes(model_config) + 4 + 4
            + _SCORE_BYTES[score_dtype])
