"""Pallas TPU kernel: a layer's attention over each device's stored
context, read where it rests in the ring's table.

Why this op: a device's context of `laguna-stream` is 1.5 MB of keys and
as much of values a full layer (768 positions of 1,024 bfloat16), 1 MB a
sliding one; of `olmo-hybrid-stream` 2.95 MB each (384 of 3,840). A
frame names 256 rows, and the equations read a row once. As XLA's
gather, the decode form's write of the position's own entry into the
gathered copy and its second reading, a row crossed HBM three times:
ten gathers were 9.85 ms of Laguna's 25.1 ms step on a v5e before the
model had read a byte, two were 4.6 of Olmo's 19.8 (PERF.md section
6). Here the two tables stay where they rest (`pl.ANY`) and the
kernel copies what it reads itself, as ops/expert_kernel.py does: the
frame's row indices and positions are prefetched scalars, a grid step
is a row, and step `i` starts row `dev[i + 1]`'s copies into the other
half of a double buffer before row `dev[i]` computes. Nothing of shape
`[frame, positions, width]` exists. The kernel only reads: the
position's own entry is in the table already, appended by the ring
before the call (scoring/stream.py `ContextAtRest`), or handed beside it
(below), and no output aliases a table.

A row copies only the prefix it has filled: `n = ceil(len / B)`
position blocks of keys and of values, `len = min(pos + 1, P)`
(`min(pos, P)` where the own entry comes beside the table), one block at
the least, `B = position_block(P)` (64 at 384, 448, 512 and 768), and
the row's products run over those `n * B` positions alone, so nothing
it did not copy reaches one. A row that has wrapped (`pos >= P`) copies
all of it. Copying whole rows, `ouro-stream`'s kernel moved 2.82 GB a
step at 90% of HBM's peak where its equations need 1.43 (PERF.md
section 5): only a shorter copy could make it faster. Each row starts
ONE copy a table whose length is one of the `P / B` static lengths,
picked by a tree of branches on `n` (`expert_kernel.pick`), and waits on
it through the same descriptor in the same branch. The grid is not split
over position blocks: a grid step costs about 0.35 us whether it copies
or not, and `ouro-stream`'s 768 row-steps a step at 7 blocks a row
would cost 1.9 ms, the whole saving.

    a row, `q` `[heads, d]`, keys and values `[P, kv * d]` as stored:
        wide   = q, head h over the lanes of key-value head h // g
        logits = wide K^T * scale           [heads, P]   f32
        probs  = softmax(logits where p <= pos)
        out    = bf16(probs) V              [heads, kv * d] f32
        o_h    = out's lanes of key-value head h // g

which are `SeqBlocks._decode_rows`' lines (models/seqblocks.py):
bfloat16 operands, float32 accumulation, softmax in float32; only the
order of a float32 sum may differ. A leaf that wraps and has wrapped has
nothing to mask: `p <= pos` says both. The block-diagonal `wide` costs
`kv` times the needed FLOPs and no more time: either operand order, each
byte of the context enters the MXU once, which is what bounds the
products (1.5 TB/s over four MXUs against HBM's 819 GB/s).

Padding (`dev >= scratch`, the table's last row) copies nothing; its
output is 0 and it writes nothing. Heads are padded to
whole `(16, 128)` tiles of `probs`; the padded rows' lanes are all zero
and are cut off again.

A key-value head of 64 lanes (`lfm2-stream`'s 8, under 32 query heads)
is half a lane tile, so a head's piece of `wide` or of `out` would be a
half-tile lane slice. Such heads are read two to a tile, by masks: `q`
comes in twice side by side, a whole tile (XLA lays it so before the
call: 8 MB at a frame of 512 and 32 heads), `wide` is that tile repeated
over the context's lane tiles and kept where a lane's head is the row's
(`_own_lanes`), and a head's output is `out` kept by the same mask, its
lane tiles summed and then a tile's two halves (a lane roll), each sum
of one term and zeros; the call hands back the tile and XLA keeps its
first 64 lanes. A `wide` built by XLA whole and an `out` folded by XLA
after the call would write and read 16 MB (bfloat16) and 33 MB (float32)
a layer there. The shape alone chooses: a head of whole lane tiles runs
the lines above and no mask.

A table whose row holds several contexts side by side, each a block of
`kv * d` lanes (`ouro-stream`'s one key table and one value table, a
block for each pass and layer: models/ouro.py), is read at the block
`block` names, one more prefetched scalar of the index map; a table of
one block a row is read as it was, by the same lines. Such a model
writes a position's 48 entries at once when its step ends (one append
an entry, each XLA's loop of row updates, was 6.6 of an 18.3 ms step on
a v5e; PERF.md section 6, PR 41), so it hands the kernel the own entry
`own` beside the table: the table's slot at `pos` is masked, and the
entry's logit and value take its place in the same softmax.

ONE table that is both keys and values (`dsv3-stream`'s latent
context, models/dsv3.py: a position's `c_kv ‖ k_rope ‖ 0`, 512 + 64
values in 640 lanes, which every one of 128 query heads reads whole) is
the call's other form, chosen by what it is handed: no `values`, and a
value width `value_width`. A row's table is read once and serves both
products:

    a row, `q` `[heads, W]` in the table's dtype, the table `C` `[P, W]`:
        logits = q C^T * scale              [heads, P]   f32
        probs  = softmax(logits where p <= pos)
        lat    = bf16(probs) C[:, :value_width]   f32 sums, handed back
                                                   in the table's dtype

which are `Dsv3StreamModel._attend_decode`'s lines, whose next product
casts `lat` to bfloat16 as this form hands it back. Handed the table
twice, as keys and as values, the two-table form would read each row
twice (503 MB a layer where 252 is the row once) and write a float32
output of the whole width (335 MB a layer) for XLA to cut. `q` comes in
bfloat16, the dtype the product reads it in (a float32 `q` is 168 MB a
layer more to read). Padding is clipped onto the scratch row, which
its block reads whole, and handed back 0. A row is 546 KB of DMA (its
context 246 KB, `q` 164 KB, the output 131 KB), about what a grid step costs of its own, so
a grid step takes `LATENT_ROWS` rows of the frame, each a block of its
own whose index is read from the prefetched row indices: at a frame of
1,024 rows of `[192, 640]` and 128 heads a call took 1.08 ms on a v5e at
one row a step, 0.88 at four, 0.85 at eight and at sixteen (553 MB:
654 GB/s).

VMEM: a whole row's keys and values twice each, of which a row fills
its prefix (`vmem_bytes`: 6.3 MB of buffers for Laguna's full layer, 4.2
its sliding one, 11.8 Olmo's, 9.7 one of Ouro's 48 blocks, 2.1 LFM2's)
and the row's small operands; the
one-table form's rows, `q` and output twice each (`latent_vmem_bytes`).
`fits` (heads of whole lane tiles), `fits_paired` (heads of half of
one) and `fits_latent` (one table) say whether a call stays under
`VMEM_LIMIT`; a leaf that none takes, or that is not bfloat16 in whole
tiles, takes the model's plain path. No `cost_estimate`
(ops/expert_kernel.py on why). Parity is pinned by tests/test_pallas.py
in interpret mode and the compile for a described v5e by
tests/test_dsv3_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

VMEM_LIMIT = 20 << 20     # the most a call may take of VMEM
HEAD_TILE = 16            # query rows come in whole bfloat16 tiles
LANES = 128               # a lane tile
HALF = LANES // 2         # a key-value head of half a lane tile
LATENT_ROWS = 8           # rows a grid step of the one-table form
MAX_BLOCKS = 16           # position blocks a row of the two-table form


def _padded(heads: int) -> int:
    return -(-heads // HEAD_TILE) * HEAD_TILE


def vmem_bytes(shape: tuple, heads: int, kv: int,
               width: int | None = None) -> int:
    """What a call over two tables of `shape` holds in VMEM, reading
    contexts of `width` lanes (a row's whole width where none is given):
    four blocks of a row, the row's own operands (the wide query, the
    wide output, logits and weights, `q` and `o` twice, a lane tile
    each at the least), and room for the compiler's own."""
    positions, width = shape[1], width or shape[2]
    hp = _padded(heads)
    small = hp * (6 * width + 12 * positions + 16 * max(width // kv, LANES))
    return 4 * 2 * positions * width + small + (2 << 20)


def _takes(shape: tuple, dtype, heads: int, kv: int, width: int | None,
           head) -> bool:
    if len(shape) != 3:
        return False
    width = width or shape[2]
    return (jnp.dtype(dtype) == jnp.bfloat16
            and shape[1] % 16 == 0 and shape[2] % width == 0
            and width % LANES == 0 and width % kv == 0 and head(width // kv)
            and heads % kv == 0
            and vmem_bytes(shape, heads, kv, width) <= VMEM_LIMIT)


def fits(shape: tuple, dtype, heads: int, kv: int,
         width: int | None = None) -> bool:
    """Whether `context_rows` takes two tables of `shape` and `dtype`
    for `heads` query heads over `kv` key-value heads of whole lane
    tiles, a context being `width` lanes of a row (all of it where none
    is given): a context `[positions, kv * d]` of bfloat16 in whole
    `(16, 128)` tiles, a row whole contexts, four contexts of which VMEM
    holds."""
    return _takes(shape, dtype, heads, kv, width, lambda d: d % LANES == 0)


def fits_paired(shape: tuple, dtype, heads: int, kv: int,
                width: int | None = None) -> bool:
    """As `fits`, for key-value heads of half a lane tile, which
    `context_rows` reads two to a tile: a context is still whole lane
    tiles."""
    return _takes(shape, dtype, heads, kv, width, lambda d: d == HALF)


def latent_vmem_bytes(shape: tuple, heads: int, value_width: int) -> int:
    """What a one-table call over a table of `shape` holds in VMEM: a
    grid step's `LATENT_ROWS` rows, their `q` and their output twice each,
    a row's float32 logits, weights and sums, and room for the
    compiler's own."""
    positions, width = shape[1:]
    hp = _padded(heads)
    blocks = 2 * 2 * (positions * width + hp * width + hp * value_width)
    return LATENT_ROWS * blocks + hp * (12 * positions + 4 * value_width) \
        + (2 << 20)


def fits_latent(shape: tuple, dtype, heads: int, value_width: int) -> bool:
    """Whether `context_rows` takes ONE table of `shape` and `dtype` as
    both keys and values for `heads` query heads that each read a row's
    whole width, the values its first `value_width` lanes: bfloat16 in
    whole `(16, 128)` tiles, a grid step's rows of which VMEM holds."""
    return (len(shape) == 3 and jnp.dtype(dtype) == jnp.bfloat16
            and shape[1] % 16 == 0 and shape[2] % LANES == 0
            and value_width % LANES == 0 and 0 < value_width <= shape[2]
            and latent_vmem_bytes(shape, heads, value_width) <= VMEM_LIMIT)


def _own_lanes(hp: int, width: int, kv: int, group: int) -> jax.Array:
    """`[hp, width]`: whether a lane is of the key-value head that query
    row `r` reads, `r // group`, where a head is `HALF` lanes."""
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (sum((row >= j * group).astype(jnp.int32) for j in range(1, kv))
            == sum((lane >= j * HALF).astype(jnp.int32)
                   for j in range(1, kv)))


def position_block(positions: int) -> int:
    """The positions a row's copy is rounded up to: the least multiple
    of 64 that divides `positions` into at most `MAX_BLOCKS` blocks (64
    at 384, 448, 512 and 768); `positions` itself where none does."""
    return next((b for b in range(64, positions, 64) if positions % b == 0
                 and positions // b <= MAX_BLOCKS), positions)


def _blocks(pos, positions: int, size: int, own: bool):
    """Position blocks of `size` that hold what a row at `pos` attends
    to in the table, one at the least: `min(pos + 1, positions)`
    positions, `min(pos, positions)` where the own entry comes beside."""
    held = jnp.minimum(pos if own else pos + 1, positions)
    return jnp.maximum((held + size - 1) // size, 1)


def reads(shape: tuple, dev, pos, own: bool = False) -> tuple:
    """What a two-table call over tables of `shape` reads: (live rows,
    positions copied of each table over them), int32 scalars."""
    rows, positions = shape[:2]
    live = dev < rows - 1
    size = position_block(positions)
    copied = _blocks(pos, positions, size, own) * size
    return (live.sum(dtype=jnp.int32),
            jnp.where(live, copied, 0).sum(dtype=jnp.int32))


def _kernel(dev_ref, pos_ref, *refs, scratch: int, kv: int, group: int,
            scale: float, own: bool, blocked: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from sitewhere_tpu.ops.expert_kernel import pick

    *refs, kbuf, vbuf, sem = refs
    if own:
        q_ref, k_hbm, v_hbm, ko_ref, vo_ref, o_ref = refs[-6:]
    else:
        q_ref, k_hbm, v_hbm, o_ref = refs[-4:]
    i, frame = pl.program_id(0), pl.num_programs(0)
    hp, d = q_ref.shape[1:]
    positions, width = kbuf.shape[1:]
    size = position_block(positions)
    # the context's lanes of a row: the block `block` names, or all of it
    lanes = (pl.ds(pl.multiple_of(refs[0][0] * width, LANES), width)
             if blocked else slice(None))
    # a key-value head of half a lane tile: `q` comes twice side by side
    half = width // kv == HALF

    def each_length(j, then):
        """`then(n)` for the static count `n` of position blocks row `j`
        copies: a tree of branches over the `positions // size` counts."""
        pick(_blocks(pos_ref[j], positions, size, own), 1,
             positions // size + 1, then)

    def copies(j, n: int, slot):
        """The copies of row `j`'s first `n` position blocks of keys and
        of values into half `slot` of the buffers."""
        held = pl.ds(0, n * size)
        return [pltpu.make_async_copy(table.at[dev_ref[j], held, lanes],
                                      buf.at[slot, held], sem.at[slot, t])
                for t, (table, buf) in enumerate(((k_hbm, kbuf),
                                                  (v_hbm, vbuf)))]

    def fetch(j):
        """Start row `j`'s copies, if it is live, into half `j % 2`."""
        @pl.when(dev_ref[j] < scratch)
        def _():
            each_length(j, lambda n: [c.start() for c in copies(j, n, j % 2)])

    @pl.when(i == 0)
    def _():
        fetch(0)

    # row `i + 1`'s copies run while row `i` computes
    @pl.when(i + 1 < frame)
    def _():
        fetch(i + 1)

    def attend(n: int):
        slot, length = i % 2, n * size
        for c in copies(i, n, slot):
            c.wait()
        keys = kbuf[slot, pl.ds(0, length)]
        values = vbuf[slot, pl.ds(0, length)]
        if half:
            mine = _own_lanes(hp, width, kv, group)
            wide = jnp.where(
                mine, jnp.concatenate([q_ref[0]] * (width // LANES), axis=1),
                0.0).astype(keys.dtype)
        else:
            row = jax.lax.broadcasted_iota(jnp.int32, (hp, d), 0)
            # a query row's own key-value head: rows `j * group ...`
            mine = [(row >= j * group) & (row < (j + 1) * group)
                    for j in range(kv)]
            q = q_ref[0]
            wide = jnp.concatenate(
                [jnp.where(m, q, 0.0) for m in mine],
                axis=1).astype(keys.dtype)
        logits = jax.lax.dot_general(
            wide, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        at = jax.lax.broadcasted_iota(jnp.int32, (hp, length), 1)
        # where the own entry comes apart, the table's slot for it is stale
        seen = at < pos_ref[i] if own else at <= pos_ref[i]
        logits = jnp.where(seen, logits, -jnp.inf)
        top = jnp.max(logits, axis=1, keepdims=True)
        if own:
            # the position's own logit: exact products summed in float32
            own_logit = jnp.sum(
                wide.astype(jnp.float32) * ko_ref[0].astype(jnp.float32),
                axis=1, keepdims=True) * scale
            top = jnp.maximum(top, own_logit)
            e_own = jnp.exp(own_logit - top)
        e = jnp.exp(logits - top)
        total = jnp.sum(e, axis=1, keepdims=True)
        if own:
            total = total + e_own
        probs = e / total
        out = jnp.dot(probs.astype(values.dtype), values,
                      preferred_element_type=jnp.float32)
        if own:
            out = out + (e_own / total).astype(values.dtype).astype(
                jnp.float32) * vo_ref[0].astype(jnp.float32)
        if half:
            # the row's own lanes, the lane tiles summed, then a tile's
            # two halves: one term of each sum is the head's, the others 0
            out = jnp.where(mine, out, 0.0)
            o = out[:, :LANES]
            for t in range(LANES, width, LANES):
                o = o + out[:, t:t + LANES]
            o_ref[0] = o + pltpu.roll(o, HALF, 1)
        else:
            o = jnp.zeros((hp, d), jnp.float32)
            for j, m in enumerate(mine):
                o = jnp.where(m, out[:, j * d:(j + 1) * d], o)
            o_ref[0] = o

    live = dev_ref[i] < scratch

    @pl.when(live)
    def _():
        each_length(i, attend)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _latent_kernel(dev_ref, pos_ref, q_ref, *refs, scratch: int,
                   scale: float):
    """A grid step's rows of the one-table form: `refs` are the table's
    rows, one block each, then the output."""
    from jax.experimental import pallas as pl

    *rows, o_ref = refs
    step = pl.program_id(0) * len(rows)
    hp, values = o_ref.shape[1:]
    for j, c_ref in enumerate(rows):
        live = dev_ref[step + j] < scratch

        @pl.when(live)
        def _():
            logits = jax.lax.dot_general(
                q_ref[j], c_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            at = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(at <= pos_ref[step + j], logits, -jnp.inf)
            e = jnp.exp(logits - jnp.max(logits, axis=1, keepdims=True))
            probs = (e / jnp.sum(e, axis=1, keepdims=True)).astype(
                c_ref.dtype)
            o_ref[j] = jnp.dot(probs, c_ref[0, :, :values],
                               preferred_element_type=jnp.float32
                               ).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[j] = jnp.zeros((hp, values), o_ref.dtype)


def _latent_rows(table, dev, pos, q, *, value_width: int, scale: float,
                 interpret: bool):
    """`context_rows`' one-table form (its docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    frame, heads, width = q.shape
    if width != table.shape[-1] or not fits_latent(
            table.shape, table.dtype, heads, value_width):
        raise ValueError(f"context_rows takes no table {table.dtype}"
                         f"{list(table.shape)} for {heads} heads reading "
                         f"{width} lanes, {value_width} of values")
    rows, positions = table.shape[:2]
    scratch = rows - 1
    hp = _padded(heads)
    q = jnp.pad(q.astype(table.dtype), ((0, 0), (0, hp - heads), (0, 0)))
    # a grid step's rows: the frame's next `per` rows, each a block of its
    # own whose index is read from the prefetched row indices
    per = math.gcd(frame, LATENT_ROWS)

    def row(j, i, dev, pos):
        return (jnp.minimum(dev[i * per + j], scratch), 0, 0)

    def frame_rows(i, *_):
        return (i, 0, 0)

    out = pl.pallas_call(
        functools.partial(_latent_kernel, scratch=scratch, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(frame // per,),
            in_specs=[pl.BlockSpec((per, hp, width), frame_rows)] + [
                pl.BlockSpec((1, positions, width),
                             functools.partial(row, j)) for j in range(per)],
            out_specs=pl.BlockSpec((per, hp, value_width), frame_rows)),
        out_shape=jax.ShapeDtypeStruct((frame, hp, value_width),
                                       table.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=(
            latent_vmem_bytes(table.shape, heads, value_width))),
        name="context_rows",
        interpret=interpret,
    )(dev, pos, q, *[table] * per)
    return out[:, :heads]


@functools.partial(jax.jit, static_argnames=("kv", "scale", "interpret",
                                             "value_width"))
def context_rows(keys: jax.Array, values: jax.Array | None, dev: jax.Array,
                 pos: jax.Array, q: jax.Array, block=None, own=None, *,
                 kv: int = 1, scale: float, interpret: bool = False,
                 value_width: int | None = None):
    """Attention of one token a row over rows `dev` `[B]` (ascending
    strictly, padding past the scratch row, which is the tables' last)
    of `keys` and `values` `[rows, P, kv * d]` bfloat16, read where they
    rest: the row's own entry is in them. `pos` `[B]`: positions `p <=
    pos` are attended to; `q` `[B, heads, d]` float32, head `h` reading
    key-value head `h // (heads / kv)`. A row wider than `kv * d` holds
    contexts side by side, and `block` (an int32 scalar, traced or not)
    is the one read: lanes `[block * kv * d, (block + 1) * kv * d)`.
    Where `own` `(k, v)` `[B, kv * d]` is given, the position's own entry
    is not in the tables yet: positions `p < pos` are read from them and
    the entry takes the place of `pos`. -> `[B, heads, d]` float32, a
    padding row's 0. Jitted, so that a step's layers of one shape trace
    and lower the kernel once between them.

    Handed no `values` and a `value_width`, ONE table `keys` `[rows, P,
    W]` is both keys and values (`fits_latent`): `q` `[B, heads, W]`, each
    head reading a row's whole width, and the values are its first
    `value_width` lanes; positions `p <= pos` are attended to, the
    row's own entry in the table. -> `[B, heads, value_width]` in the
    table's dtype, float32 sums rounded once, a padding row's 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if values is None:
        if block is not None or own is not None or kv != 1:
            raise ValueError("context_rows reads one table whole, with "
                             "no block, own entry or key-value heads")
        return _latent_rows(keys, dev, pos, q, value_width=value_width,
                            scale=scale, interpret=interpret)
    frame, heads, d = q.shape
    width = kv * d
    if (keys.shape != values.shape or keys.dtype != values.dtype
            or not (fits(keys.shape, keys.dtype, heads, kv, width)
                    or fits_paired(keys.shape, keys.dtype, heads, kv, width))
            or (block is None) != (keys.shape[2] == width)):
        raise ValueError(f"context_rows takes no tables {keys.dtype}"
                         f"{list(keys.shape)} for {heads} heads on {kv}")
    rows, positions = keys.shape[:2]
    scratch = rows - 1
    hp = _padded(heads)
    q = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, hp - heads), (0, 0)))
    if d == HALF:
        # heads of half a lane tile: the query and the output a whole one
        q = jnp.concatenate([q, q], axis=2)
    lanes = q.shape[2]
    scalars = (dev, pos) if block is None else (
        dev, pos, jnp.asarray(block, jnp.int32).reshape(1))

    def frame_row(i, *_):
        return (i, 0, 0)

    # the tables stay where they rest: the kernel copies a row's prefix
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, hp, lanes), frame_row), hbm, hbm]
    operands = (q, keys, values)
    if own is not None:
        in_specs += [pl.BlockSpec((1, 1, width), frame_row)] * 2
        operands += tuple(e.astype(keys.dtype).reshape(frame, 1, width)
                          for e in own)
    out = pl.pallas_call(
        functools.partial(_kernel, scratch=scratch, kv=kv,
                          group=heads // kv, scale=scale,
                          own=own is not None, blocked=block is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(frame,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hp, lanes), frame_row),
            scratch_shapes=[pltpu.VMEM((2, positions, width), keys.dtype),
                            pltpu.VMEM((2, positions, width), keys.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((frame, hp, lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(keys.shape, heads, kv, width)),
        name="context_rows",
        interpret=interpret,
    )(*scalars, *operands)
    return out[:, :heads, :d]
