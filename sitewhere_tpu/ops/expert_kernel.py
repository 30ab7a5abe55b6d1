"""Pallas TPU kernel: a layer's held experts over their tiles of tokens.

Why this op: a held expert of `dsv3-stream` sees a few dozen tokens a
step, one of `laguna-stream` about ten (models/seqblocks.py `routed`),
so its three products are bound by reading the expert's weights (88 MB
in bf16 at DeepSeek-V3's published widths, 0.1 ms at a v5e's 819 GB/s;
19 MB at Laguna-S-2.1's), not by arithmetic. Every size is read off
the shapes it is handed: the held experts (16 of 7168 x 2048, 32 of
3072 x 1024, 64 of 2048 x 1536), the tile, the tokens. One call takes the
whole layer: every expert's leaves stay where they rest in HBM, one
leaf an expert a projection, and are streamed through VMEM once, in
blocks of the intermediate width, one expert after another with no gap
between them:

    step s = (expert e, block j):
        g = x_e @ gate_e[:, j]        [tile, block]  f32
        u = x_e @ up_e[:, j]
        acc (+)= bf16(silu(g) * u) @ down_e[j, :]    [tile, hidden] f32
    after e's last block, for each of its pairs i:
        out[rows[e, i]] += acc[i] * wts[e, i]

While step `s` computes, the three weight blocks of step `s + 1` (the
next expert's first blocks after an expert's last) are on their way
into the other half of a double buffer, and expert `e + 1`'s token tile
is fetched during expert `e`. The layer's output `[tokens, hidden]`
lives in VMEM for the whole call, so the sum over a token's experts is
a row added in place, and is written out once at the end. (Measured on
a v5e, PERF.md PR 29: 118 us an expert, 746 GB/s; the same sum as 16
XLA scatter-adds of 128 rows cost 33 us each.)

Which leaves step `s + 1` copies from is known only at run time, and
each copy names its leaf statically: `fetch` finds the expert with
`pick`, a tree of two-way branches over `[0, held)` halved at each
level, so a step tests the expert's index `ceil(log2 held)` times (6 at
64 held, none at 1). A chain of one `pl.when` a held expert tested it
64 times a step at 64 held of 2048 x 1536, `lfm2-stream`'s, where a
step's copies take 1.9 us: the kernel alone took 3.08 ms a call there,
392 GB/s, and takes 1.68 with the tree, 717 GB/s; at 32 of 3072 x
1024, Laguna's, 0.895 and 0.881 ms (PERF.md PR 40, ROADMAP S17).

An expert is gated or not by the leaves it is handed: three (`gate`,
`up`, `down`) are the SiLU-gated form above; two (`up`, `down`) the
ungated `relu` squared form of `nemotron-h-stream`'s latent experts
(models/nemotron_h.py, 64 held of 1024 x 2688), whose step reads two
blocks and computes

        u = x_e @ up_e[:, j]
        acc (+)= bf16(relu(u)^2) @ down_e[j, :]

with the same streaming, the same double buffer and the same sums.

Numbers as the plain path's (`SeqBlocks._mlp`): operands bf16,
accumulation f32, `silu(g) * u` (or `relu(u)^2`) in f32 and rounded to
bf16 once before the down product, the pair's weight applied in f32, the
sum over a token's experts in f32, expert by expert. The down product
is summed block by block in f32, so the two paths differ by the order
of a float32 sum.

VMEM: the output (28 MiB for 1,024 tokens at hidden 7168, 3 MiB for 256
at 3072), the token tile twice, one tile of sums, the weight blocks of a
step twice (`BLOCK` columns: 10.5 MiB for three at hidden 7168).
`vmem_bytes` is the sum, and `fits` says whether a call stays under
`VMEM_LIMIT`: what XLA keeps in VMEM across the call (the residual
stream, the shared expert's output) has to stay there, and on a v5e's
128 MiB a call of 52 MiB left the rest of the step as it was where one
of 64 MiB slowed it by a millisecond a layer. The call declares no
`cost_estimate`: given one, the compiler planned the whole step's VMEM
round the call and every layer's softmax fusion fell to a third of its
window (732 for 557 us each, 2.2 ms a step). Parity is pinned by
tests/test_pallas.py in interpret mode and the compile for a described
v5e by tests/test_dsv3_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = 128               # columns of the intermediate width a step
VMEM_LIMIT = 52 << 20     # the most a call may take of VMEM


GATED, UNGATED = ("gate", "up", "down"), ("up", "down")


def vmem_bytes(tokens: int, hidden: int, tile: int, leaves: int = 3) -> int:
    """What a call holds in VMEM, with room for the compiler's own: the
    blocks of `leaves` leaves an expert twice."""
    return (4 * tokens * hidden + (2 * 2 + 4 + 4) * tile * hidden
            + 4 * leaves * hidden * BLOCK + (1 << 20))


def fits(tokens: int, hidden: int, inter: int, tile: int,
         leaves: int = 3) -> bool:
    """Whether `expert_tiles` takes these shapes: whole lane tiles, whole
    sublane tiles of bf16 rows, and a layer's output that VMEM holds."""
    return (hidden % 128 == 0 and inter % BLOCK == 0 and tile % 16 == 0
            and tokens % 8 == 0
            and vmem_bytes(tokens, hidden, tile, leaves) <= VMEM_LIMIT)


def pick(e, lo: int, hi: int, start) -> None:
    """`start(k)` for the one static `k` of `[lo, hi)` that the traced `e`
    equals: halve the range at each of `ceil(log2(hi - lo))` two-way
    branches, so that a step tests `e` that many times, not once a held
    expert."""
    if hi - lo == 1:
        start(lo)
        return
    mid = (lo + hi) // 2
    jax.lax.cond(e < mid, lambda: pick(e, lo, mid, start),
                 lambda: pick(e, mid, hi, start))


def _kernel(rows_ref, wts_ref, counts_ref, xs_ref, *rest, held: int,
            steps: int, tile: int, names: tuple):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(names)
    leaves, out_ref = rest[:n * held], rest[n * held]
    xbuf, *bufs = rest[n * held + 1:n * held + 2 + n]
    acc, out, wsem, xsem, osem = rest[n * held + 2 + n:]

    def weights(e: int, j, slot):
        """The copies of block `j` of expert `e`'s leaves (static) into
        half `slot` of the double buffer."""
        cols = pl.ds(pl.multiple_of(j * BLOCK, BLOCK), BLOCK)
        return tuple(
            pltpu.make_async_copy(
                (leaf.at[cols, :] if name == "down" else leaf.at[:, cols]),
                buf.at[slot], wsem.at[slot, i])
            for i, (name, leaf, buf) in enumerate(
                zip(names, leaves[n * e:n * e + n], bufs)))

    def fetch(s):
        """Start step `s`'s weights; which leaves is found at run time,
        the copies themselves are static."""
        j, slot = s % steps, s % 2

        def start(e: int):
            for copy in weights(e, j, slot):
                copy.start()

        pick(s // steps, 0, held, start)

    def tokens(e, half):
        return pltpu.make_async_copy(
            xs_ref.at[pl.ds(pl.multiple_of(e * tile, tile), tile)],
            xbuf.at[half], xsem.at[half])

    for copy in weights(0, 0, 0):
        copy.start()
    tokens(0, 0).start()
    out[...] = jnp.zeros_like(out)

    def step(s, carry):
        e, j, slot, half = s // steps, s % steps, s % 2, (s // steps) % 2

        @pl.when(s + 1 < held * steps)
        def _():
            fetch(s + 1)

        @pl.when(j == 0)
        def _():
            tokens(e, half).wait()

            @pl.when(e + 1 < held)
            def _():
                tokens(e + 1, 1 - half).start()

        for copy in weights(0, j, slot):
            copy.wait()
        x = xbuf[half]
        if n == 3:
            g = jnp.dot(x, bufs[0][slot], preferred_element_type=jnp.float32)
            u = jnp.dot(x, bufs[1][slot], preferred_element_type=jnp.float32)
            h = jax.nn.silu(g) * u
        else:
            u = jnp.dot(x, bufs[0][slot], preferred_element_type=jnp.float32)
            h = jnp.square(jnp.maximum(u, 0.0))
        y = jnp.dot(h.astype(x.dtype), bufs[-1][slot],
                    preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _():
            acc[...] = y

        @pl.when(j > 0)
        def _():
            acc[...] += y

        @pl.when(j == steps - 1)
        def _():
            def combine(i, carry):
                at = e * tile + i
                out[pl.ds(rows_ref[at], 1), :] += (
                    acc[pl.ds(i, 1), :] * wts_ref[at])
                return carry

            jax.lax.fori_loop(0, jnp.minimum(counts_ref[e], tile), combine, 0)

        return carry

    jax.lax.fori_loop(0, held * steps, step, 0)
    done = pltpu.make_async_copy(out, out_ref, osem.at[0])
    done.start()
    done.wait()


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def expert_tiles(experts: list, xs: jax.Array, rows: jax.Array,
                 wts: jax.Array, counts: jax.Array, tokens: int,
                 interpret: bool = False) -> jax.Array:
    """`[tokens, hidden]` f32: zero, plus, for each of the `held`
    `experts` (`gate`, `up` `[hidden, inter]`, `down` `[inter, hidden]`,
    bf16; or `up` and `down` alone) and each of the first `min(counts[e],
    tile)` rows `i` of its tile of `xs` `[held * tile, hidden]` bf16,
    `(silu(x gate) * (x up)) down * wts[e * tile + i]` (ungated:
    `relu(x up)^2 down * ...`) added to row `rows[e * tile + i]`.
    Jitted, so that a step's expert layers trace and lower the kernel
    once between them, not once a layer (a second of set-up each)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    held = len(experts)
    names = GATED if "gate" in experts[0] else UNGATED
    hidden, inter = experts[0]["up"].shape
    tile = xs.shape[0] // held
    if not fits(tokens, hidden, inter, tile, len(names)):
        raise ValueError(f"expert_tiles takes no {tokens} tokens of "
                         f"{hidden}, tiles of {tile}, experts of {inter}")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, held=held, steps=inter // BLOCK,
                          tile=tile, names=names),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(),
            in_specs=[hbm] * (1 + len(names) * held), out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((2, tile, hidden), xs.dtype),
                *(pltpu.VMEM((2, BLOCK, hidden) if name == "down"
                             else (2, hidden, BLOCK), xs.dtype)
                  for name in names),
                pltpu.VMEM((tile, hidden), jnp.float32),
                pltpu.VMEM((tokens, hidden), jnp.float32),
                pltpu.SemaphoreType.DMA((2, len(names))),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(tokens, hidden, tile, len(names))),
        name="expert_tiles",
        interpret=interpret,
    )(rows, wts, counts, xs, *(e[name] for e in experts for name in names))
