"""Pallas TPU kernel: a layer's matrix states, updated where they rest
in the ring's table.

Why this op: a row's state of `olmo-hybrid-stream` (models/olmo_hybrid.py)
is 2.2 MB at the published widths (15 pairs of heads, 96 keys, two heads
of 192 values side by side in 384 lanes, float32), one of
`nemotron-h-stream`'s Mamba-2 layers (models/nemotron_h.py) 4 MiB (64
pairs of heads, 128 state channels, two heads of 64 in a lane tile), a
frame names 128 to 256 rows, and the recurrence reads a row once and
writes it once. As
a gather, two passes and a scatter of XLA's the same 2.2 MB crossed HBM
six times (6.79 ms a layer on a v5e, PERF.md section 6, PR 36). Here
the table itself is the kernel's input AND its output, aliased, and the
frame's row indices are prefetched scalars that the block index is read
from: the pipeline brings row `dev[i + 1]` into VMEM and takes row
`dev[i - 1]` out while row `dev[i]` computes, and nothing else of the
table moves.

The streaming is written once; the update it applies is one of two, and
which is read off the vectors a row brings (`vec`'s second dimension),
never off a model:

    a row, for each group `g` of heads that share a row of lanes:
        kw, qw = the heads' key and query, each over its head's lanes
        sq = sum_d S qw;  largest = max |S|
      the gated delta rule (four vectors: v, alpha, beta, k . q):
        sk     = sum_d S kw
        write  = beta (v - alpha sk)
      a decay and a write (three vectors: v, alpha, k . q):
        write  = v
      then, both:
        o      = alpha sq + (k . q) write
        S     <- alpha S + kw write

The first is `OlmoHybridStreamModel._gdn_cell`'s (`S <- alpha S; r = v -
S^T k; S <- S + k (beta r)^T; o = S^T q`), the second
`NemotronHStreamModel._ssm_cell`'s (Mamba-2: `S <- a S + B (dt x)^T; y =
S^T C`, with `B`, `C` as keys and queries and `dt x` as the values),
float32 on float32 operands; the sum over a head's keys may run in
another order. What a row brings beside its state is small: keys and
queries `[2, dk, K]` (keys down the sublanes, a column a head, or one a
group of heads where a group's heads share them) and the vectors over
the lanes `[n, G, lanes]`. Nothing of the state's own shape is ever an
operand: that would be a pass over HBM again.

A row that four blocks of in VMEM would pass `VMEM_LIMIT` is taken in
blocks of its groups (`blocks`: the fewest that fit, 2 for a Mamba-2
row), one grid step a block, the keys and vectors laid out a block at a
time; a row that fits whole is one block.

Padding (`dev >= scratch`, the table's last row) is clipped onto the
scratch row, whose block is handed back as it came and whose `o` is 0,
so a step writes the rows it was given and no other, as the ring
promises (scoring/stream.py). Rows ascend strictly, so no block is in
flight twice; the pipeline's own copies have all landed when the call
returns.

VMEM: a block twice in and twice out, and the small operands
(`vmem_bytes`: 9.6 MB for Olmo's whole row, 9.0 MB for half a Mamba-2
row). `fits` says whether some number of blocks stays under
`VMEM_LIMIT`; a leaf that does not fit, or is not float32 in whole `(8,
128)` tiles, takes the model's plain path. No `cost_estimate`
(ops/expert_kernel.py on why). Parity is pinned by tests/test_pallas.py
in interpret mode and the compile for a described v5e by
tests/test_dsv3_tpu_compile.py.
"""

from __future__ import annotations

import functools
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


def vmem_bytes(shape: tuple, blocks: int = 1) -> int:
    """What a call over a table of `shape`, a row in `blocks` blocks of
    its groups, holds in VMEM: four blocks, the small operands twice, and
    room for the compiler's own."""
    groups, keys, lanes = shape[1:]
    groups //= blocks
    small = 2 * keys * 128 + (4 + 1) * (groups + 8) * lanes
    return 4 * (4 * groups * keys * lanes + 2 * small) + (1 << 19)


def blocks(shape: tuple) -> int:
    """The fewest blocks of its groups that a row of `shape` is taken in
    for four of them to stay under `VMEM_LIMIT`; 0 where none do."""
    return next((n for n in range(1, shape[1] + 1) if shape[1] % n == 0
                 and vmem_bytes(shape, n) <= VMEM_LIMIT), 0)


def fits(shape: tuple, dtype) -> bool:
    """Whether `update_rows` takes a table of `shape` and `dtype`: a row
    `[groups, keys, lanes]` of float32 in whole `(8, 128)` tiles, four
    blocks of which VMEM holds."""
    return (len(shape) == 4 and jnp.dtype(dtype) == jnp.float32
            and shape[2] % 8 == 0 and shape[3] % 128 == 0
            and blocks(shape) > 0)


def _kernel(dev_ref, s_ref, keys_ref, vec_ref, next_ref, out_ref, *,
            scratch: int, group: int):
    from jax.experimental import pallas as pl

    groups, dk, lanes = s_ref.shape[1:]
    dv = lanes // group
    delta = vec_ref.shape[1] == 4
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
            if delta:
                v, alpha, beta, kq = (vec_ref[0, j, g:g + 1, :]
                                      for j in range(4))
                sk = jnp.sum(s * kw, axis=0, keepdims=True)
            else:
                v, alpha, kq = (vec_ref[0, j, g:g + 1, :] for j in range(3))
            sq = jnp.sum(s * qw, axis=0, keepdims=True)
            largest = jnp.maximum(
                largest, jnp.max(jnp.abs(s), axis=0, keepdims=True))
            write = beta * (v - alpha * sk) if delta else v
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
    """The recurrence on rows `dev` `[B]` (ascending strictly, padding
    past the scratch row, which is the table's last) of `table` `[rows,
    G, dk, lanes]` float32, where they rest. `keys` `[B, 2, dk, K]`: a
    row's keys, then its queries, a column a head (`K / G` heads to a
    row of lanes, each over its own lanes); `vec` `[B, 4, G, lanes]`:
    `v`, `alpha`, `beta` and `k . q`, the gated delta rule, or `[B, 3,
    G, lanes]`: `v`, `alpha` and `k . q`, a decay and a write; a head's
    number over the head's lanes. -> (the table, which is the donated
    one where the caller donates it; `o` `[B, G, lanes]`; `[B]` the
    largest magnitude the row's state held before the update; a padding
    row's are 0). Jitted, so that a step's layers trace and lower the
    kernel once between them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not fits(table.shape, table.dtype) or vec.shape[1] not in (3, 4):
        raise ValueError(f"update_rows takes no table {table.dtype}"
                         f"{list(table.shape)} with {vec.shape[1]} vectors")
    rows, groups, dk, lanes = table.shape
    frame, heads = dev.shape[0], keys.shape[-1]
    scratch = rows - 1
    n = blocks(table.shape)
    size = groups // n
    # a block's keys, vectors and outputs are one leading index apart:
    # row `i`'s block `j` at `i * n + j`
    keys = keys.reshape(frame, 2, dk, n, heads // n).transpose(
        0, 3, 1, 2, 4).reshape(frame * n, 2, dk, heads // n)
    vec = vec.reshape(frame, -1, n, size, lanes).swapaxes(1, 2).reshape(
        frame * n, -1, size, lanes)

    def row(i, j, dev):
        return (jnp.minimum(dev[i], scratch), j, 0, 0)

    def mine(i, j, dev):
        return (i * n + j, 0, 0, 0)

    state = pl.BlockSpec((1, size, dk, lanes), row)
    table, out = pl.pallas_call(
        functools.partial(_kernel, scratch=scratch, group=heads // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(frame, n),
            in_specs=[
                state,
                pl.BlockSpec((1, 2, dk, heads // n), mine),
                pl.BlockSpec((1, vec.shape[1], size, lanes), mine)],
            out_specs=[
                state,
                pl.BlockSpec((1, size + 1, lanes),
                             lambda *at: mine(*at)[:3])]),
        out_shape=[
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct((frame * n, size + 1, lanes), jnp.float32)],
        # operand 0 is `dev`: the table comes in second and goes out first
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(table.shape, n)),
        name="state_rows",
        interpret=interpret,
    )(dev, table, keys, vec)
    # a block's `o`, then its largest magnitude: back to the row's
    out = out.reshape(frame, n, size + 1, lanes)
    return (table, out[:, :, :size].reshape(frame, groups, lanes),
            out[:, :, size, 0].max(1))
