"""Pallas TPU kernel: fused windowed-LSTM recurrence → final hidden.

Why this op: the windowed anomaly scorer re-runs a W-step LSTM over
every flushed device window (models/lstm.py `_predictions`) and scoring
only consumes the LAST step's prediction, so the kernel form is: keep
h/c and both weight matrices resident in VMEM, run the whole recurrence
in one kernel invocation per batch tile, and write back ONLY the final
h — O(B·h) HBM writes instead of O(B·h·W) intermediate traffic.
(Training still wants every step's output for the loss; it keeps the
lax.scan path in models/common.py.) Whether that beats XLA's own scan
on a chip is not measured; see PERF.md.

Layout (what Mosaic on a v5e accepts): everything is TRANSPOSED so the
batch rides the 128-wide lane axis and the loop index lands on a
sublane row —

    x      [T, B_TILE]        one row per step, `x_ref[pl.ds(t, 1), :]`
    h, c   [hidden, B_TILE]   loop carries (vregs), f32
    gates  [4·hidden, B_TILE] = whT @ h  +  wxT · x_t  +  bT

so the i/f/g/o slices fall on sublane offsets that are multiples of 8
(hidden 64), the recurrent matmul is [4h, h] @ [h, B_TILE] on the MXU
in bf16 with f32 accumulation, and the d_in = 1 input projection is a
broadcast multiply in f32 (a K = 1 matmul has no MXU form). The
batch-major layout this replaced — `x_ref[:, pl.ds(t, 1)]`, a dynamic
width-1 slice on the lane axis — is refused by the compiler ("cannot
statically prove that index in dimension 1 is a multiple of 128").
VMEM per program at T = 63, hidden 64: x 64 KiB + out 64 KiB, double
buffered, plus ~130 KiB of weights — far under the 16 MiB scoped limit.

Semantics match `lstm_scan(params, xn[:, :-1, None], bf16)[1][0]`: x and
both weight matrices are rounded to bf16 exactly as the scan rounds
them, products accumulate in f32, gates/state stay f32.

The pure-jax reference path (`_reference_final`) serves CPU runs,
multi-layer configs, and batch sizes the tile doesn't divide;
`pallas_ok()` is the selection predicate. Parity is pinned by
tests/test_pallas.py in interpret mode and by chip_smoke.py phase B on
the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

B_TILE = 256          # batch columns per kernel program (two lane tiles)


def _kernel(x_ref, wx_ref, wh_ref, b_ref, out_ref, *, steps: int,
            hidden: int):
    """One batch tile: run `steps` cell updates with everything in VMEM."""
    from jax.experimental import pallas as pl

    wx, wh, b = wx_ref[...], wh_ref[...], b_ref[...]

    def step(t, carry):
        h, c = carry
        xt = x_ref[pl.ds(t, 1), :]                             # [1, Bt]
        gates = (wx * xt
                 + jnp.dot(wh, h.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
                 + b)                                          # [4h, Bt]
        i = gates[:hidden]
        f = gates[hidden:2 * hidden]
        g = gates[2 * hidden:3 * hidden]
        o = gates[3 * hidden:]
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return h, c

    zeros = jnp.zeros(out_ref.shape, jnp.float32)
    h, _ = jax.lax.fori_loop(0, steps, step, (zeros, zeros))
    out_ref[...] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_final(xn, wx, wh, b, *, interpret: bool = False):
    """xn [B, T] f32, wx [1, 4h] / wh [h, 4h] bf16, b [1, 4h] f32 →
    final h [B, h] f32. The transposes into and out of the kernel's
    batch-on-lanes layout are plain XLA ops inside the same jit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T = xn.shape
    hidden = wh.shape[0]
    kernel = functools.partial(_kernel, steps=T, hidden=hidden)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0),
                            memory_space=pltpu.VMEM)

    h_t = pl.pallas_call(
        kernel,
        grid=(B // B_TILE,),
        in_specs=[
            pl.BlockSpec((T, B_TILE), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            whole((4 * hidden, 1)),
            whole((4 * hidden, hidden)),
            whole((4 * hidden, 1)),
        ],
        out_specs=pl.BlockSpec((hidden, B_TILE), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((hidden, B), jnp.float32),
        interpret=interpret,
    )(xn.astype(jnp.bfloat16).astype(jnp.float32).T,
      wx.astype(jnp.float32).T, wh.T, b.T)
    return h_t.T


def _reference_final(params_layer: dict, xn: jax.Array, cdt) -> jax.Array:
    """Pure-jax twin (models/common.lstm_scan, final h only)."""
    from sitewhere_tpu.models.common import lstm_scan

    _, (h, _c) = lstm_scan(params_layer, xn[:, :, None], cdt)
    return h


def pallas_ok(batch: int, layers: int, cdt=jnp.bfloat16) -> bool:
    """Selection predicate: the kernel covers the single-layer bf16
    scorer on a TPU for tile-divisible batches (bench buckets are powers
    of two ≥ 256). Everything else — including a model built with a
    non-bf16 compute_dtype, whose matmuls the kernel would silently
    narrow — takes the reference path."""
    return (layers == 1 and batch >= B_TILE and batch % B_TILE == 0
            and cdt == jnp.bfloat16
            and jax.devices()[0].platform == "tpu")


def lstm_window_final(params_layer: dict, xn: jax.Array, cdt,
                      use_pallas: bool | None = None,
                      interpret: bool = False) -> jax.Array:
    """Final hidden state of a single-layer LSTM over xn[:, :T].

    xn: [B, T] f32 normalized inputs (caller already dropped the last
    window slot). `use_pallas=None` selects via `pallas_ok`; the
    kernel path computes bf16 matmuls, so non-bf16 `cdt` never selects
    it."""
    if use_pallas is None:
        use_pallas = pallas_ok(xn.shape[0], layers=1, cdt=cdt)
    if not use_pallas:
        return _reference_final(params_layer, xn, cdt)
    wx = params_layer["wx"].astype(jnp.bfloat16)
    wh = params_layer["wh"].astype(jnp.bfloat16)
    b = params_layer["b"].reshape(1, -1)
    return _pallas_final(xn, wx, wh, b, interpret=interpret)
