"""Pallas TPU kernel: a linear layer's matrix states, updated where they
rest in the ring's table.

Why this op: a row's state of `olmo-hybrid-stream` (models/olmo_hybrid.py)
is 2.2 MB at the published widths (15 pairs of heads, 96 keys, two heads
of 192 values side by side in 384 lanes, float32), a frame names 256
rows, and the gated delta rule reads a row once and writes it once. As
a gather, two passes and a scatter of XLA's the same 2.2 MB crossed HBM
six times (6.79 ms a layer on a v5e, PERF.md section 6, PR 36). Here
the table itself is the kernel's input AND its output, aliased, and the
frame's row indices are prefetched scalars that the block index is read
from: the pipeline brings row `dev[i + 1]` into VMEM and takes row
`dev[i - 1]` out while row `dev[i]` computes, and nothing else of the
table moves.

    a row, for each group `g` of heads that share a row of lanes:
        kw, qw = the heads' key and query, each over its head's lanes
        sk, sq = sum_d S kw, sum_d S qw;  largest = max |S|
        write  = beta (v - alpha sk)
        o      = alpha sq + (k . q) write
        S     <- alpha S + kw write

which are `OlmoHybridStreamModel._gdn_cell`'s lines (`S <- alpha S; r =
v - S^T k; S <- S + k (beta r)^T; o = S^T q`), float32 on float32
operands; the sum over a head's keys may run in another order. What a
row brings beside its state is small: keys and queries `[2, dk, H]`
(keys down the sublanes, a head a lane) and four vectors over the lanes
`[4, H / g, lanes]`. Nothing of the state's own shape is ever an
operand: that would be a pass over HBM again.

Padding (`dev >= scratch`, the table's last row) is clipped onto the
scratch row, whose block is handed back as it came and whose `o` is 0,
so a step writes the rows it was given and no other, as the ring
promises (scoring/stream.py). Rows ascend strictly, so no block is in
flight twice; the pipeline's own copies have all landed when the call
returns.

VMEM: a row's block twice in and twice out, and the small operands
(`vmem_bytes`: 9.6 MB at the published widths). `fits` says whether a
call stays under `VMEM_LIMIT`; a leaf that does not fit, or is not
float32 in whole `(8, 128)` tiles, takes the model's plain path. No
`cost_estimate` (ops/expert_kernel.py on why). Parity is pinned by
tests/test_pallas.py in interpret mode and the compile for a described
v5e by tests/test_dsv3_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math
import threading

import jax
import jax.numpy as jnp

VMEM_LIMIT = 12 << 20     # the most a call may take of VMEM


def import_ahead() -> None:
    """Start importing Pallas on a thread where this process's backend
    is a TPU, and return. Nothing caches its 118 modules' bytecode on the
    serving machine, so the import took 1.8 s of a start when it stood in
    line before the step's first trace (PERF.md section 6, PR 29); begun
    where seeding is first traced, it runs while the main thread waits on
    the chip for the seeding calls. `update_rows`' own import statement is
    the join: it waits on the import system's lock for a module that is
    on its way in, and raises what the thread's import raised."""
    def work():
        if jax.default_backend() == "tpu":
            from jax.experimental.pallas import tpu  # noqa: F401

    threading.Thread(target=work, name="pallas-import", daemon=True).start()


def vmem_bytes(shape: tuple) -> int:
    """What a call over a table of `shape` holds in VMEM: four blocks of
    a row, the small operands twice, and room for the compiler's own."""
    groups, keys, lanes = shape[1:]
    small = 2 * keys * 128 + (4 + 1) * (groups + 8) * lanes
    return 4 * (4 * math.prod(shape[1:]) + 2 * small) + (1 << 19)


def fits(shape: tuple, dtype) -> bool:
    """Whether `update_rows` takes a table of `shape` and `dtype`: a row
    `[groups, keys, lanes]` of float32 in whole `(8, 128)` tiles, four of
    which VMEM holds."""
    return (len(shape) == 4 and jnp.dtype(dtype) == jnp.float32
            and shape[2] % 8 == 0 and shape[3] % 128 == 0
            and vmem_bytes(shape) <= VMEM_LIMIT)


def _kernel(dev_ref, s_ref, keys_ref, vec_ref, next_ref, out_ref, *,
            scratch: int, group: int):
    from jax.experimental import pallas as pl

    groups, dk, lanes = s_ref.shape[1:]
    dv = lanes // group
    live = dev_ref[pl.program_id(0)] < scratch

    @pl.when(live)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, lanes), 1)
        k, q = keys_ref[0, 0], keys_ref[0, 1]

        def wide(x, g):
            """Heads `g * group ...` of `x` `[dk, H]`, each over its own
            lanes: `[dk, lanes]`."""
            first = g * group
            out = jnp.broadcast_to(x[:, first:first + 1], (dk, lanes))
            for j in range(1, group):
                out = jnp.where(lane >= j * dv,
                                x[:, first + j:first + j + 1], out)
            return out

        largest = jnp.zeros((1, lanes), jnp.float32)
        for g in range(groups):
            s = s_ref[0, g]
            kw, qw = wide(k, g), wide(q, g)
            v, alpha, beta, kq = (vec_ref[0, j, g:g + 1, :]
                                  for j in range(4))
            sk = jnp.sum(s * kw, axis=0, keepdims=True)
            sq = jnp.sum(s * qw, axis=0, keepdims=True)
            largest = jnp.maximum(
                largest, jnp.max(jnp.abs(s), axis=0, keepdims=True))
            write = beta * (v - alpha * sk)
            out_ref[0, g:g + 1, :] = alpha * sq + kq * write
            next_ref[0, g] = alpha * s + kw * write
        out_ref[0, groups:groups + 1, :] = jnp.broadcast_to(
            jnp.max(largest, axis=1, keepdims=True), (1, lanes))

    @pl.when(jnp.logical_not(live))
    def _():
        next_ref[...] = s_ref[...]
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def update_rows(table: jax.Array, dev: jax.Array, keys: jax.Array,
                vec: jax.Array, interpret: bool = False):
    """The delta rule on rows `dev` `[B]` (ascending strictly, padding
    past the scratch row, which is the table's last) of `table` `[rows,
    G, dk, lanes]` float32, where they rest. `keys` `[B, 2, dk, H]`: a
    row's keys, then its queries, a head a lane; `vec` `[B, 4, G,
    lanes]`: `v`, `alpha`, `beta` and `k . q`, a head's number over the
    head's lanes. -> (the table, which is the donated one where the
    caller donates it; `[B, G + 1, lanes]`: `o`, then in every lane of
    the last row the largest magnitude the row's state held before the
    update; a padding row's are 0). Jitted, so that a step's linear
    layers trace and lower the kernel once between them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not fits(table.shape, table.dtype):
        raise ValueError(f"update_rows takes no table {table.dtype}"
                         f"{list(table.shape)}")
    rows, groups, dk, lanes = table.shape
    frame, heads = dev.shape[0], keys.shape[-1]
    scratch = rows - 1

    def row(i, dev):
        return (jnp.minimum(dev[i], scratch), 0, 0, 0)

    state = pl.BlockSpec((1, groups, dk, lanes), row)
    return pl.pallas_call(
        functools.partial(_kernel, scratch=scratch, group=heads // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(frame,),
            in_specs=[
                state,
                pl.BlockSpec((1, 2, dk, heads), lambda i, dev: (i, 0, 0, 0)),
                pl.BlockSpec((1, 4, groups, lanes),
                             lambda i, dev: (i, 0, 0, 0))],
            out_specs=[
                state,
                pl.BlockSpec((1, groups + 1, lanes),
                             lambda i, dev: (i, 0, 0))]),
        out_shape=[
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct((frame, groups + 1, lanes), jnp.float32)],
        # operand 0 is `dev`: the table comes in second and goes out first
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(table.shape)),
        name="state_rows",
        interpret=interpret,
    )(dev, table, keys, vec)
