"""Device-resident streaming state ring: per-device model state in HBM.

The streaming twin of `scoring/ring.py`'s window ring. Where DeviceRing
stores raw history and re-scores the whole window per event, this ring
stores the model's OWN recurrent state (h/c, standing prediction,
normalization stats — whatever the model's `init_state` declares) and a
flush is one fused jit:

    gather state rows → model.step_score (one cell step) → scatter back

donated in place, uploading only (device id, value) deltas exactly like
the window ring. Per-event device cost drops from a W-step rescan to one
step (~63× for the W=64 LSTM), which moves the throughput ceiling back
to the host pipeline where batching can fight it.

Contract with the model (see `StreamingLstmModel` in models/lstm.py):
    init_state(cap)            -> dict of [cap, ...] leaves
    step_score(params, rows, v) -> (scores, new rows)
    warm_state(params, x, valid) -> state dict (host-window replay seed)
A leaf of two or more dimensions declares a minor dimension of whole
128-lane tiles (the model packs and pads; the ring knows no layout):
a `[rows, 64]` leaf rests column-major on a TPU, and each step copied
both tables to row-major and back round their row scatters, 4.8 of a
6.2 ms step over 524,289 rows on a v5e (PERF.md section 6, PR 27).
Per-row scalars of a fleet-sized table belong in the row leaf: a
one-dimensional leaf costs a gather and a scatter of its own, 0.12 to
0.39 and 0.08 ms at 16,384 of 524,289 rows (PERF.md section 6, PR 31).
A row of more than one tile is better declared `[rows, k, 128]` than
`[rows, k * 128]`: the compiler's row scatter of 16,384 rows took 1.03
ms into the first and 1.45 into the second (the same bytes at rest).

Contract with the engines: the rows of a step, `dev`, ascend strictly,
padding included. Padding takes the indices past the table's last row,
one each (`pad_rows`): the gather clips them onto the scratch row, the
scatter drops them, so a step writes the rows it was given and no
other. Every scatter says `unique_indices` (`DISTINCT_ROWS`): an
engine that names a row twice in one step gets a table that no test of
the step alone would catch. Both engines sort a take that does not
ascend and split one with repeats into rounds (scoring/settle.py
`occurrence_rounds`). That the rows ascend
is said to the gathers only: told so, a v5e's compiler made the row
scatter a sixth slower and a context append thirty times (PERF.md
section 6, PR 31).

A model may declare WINDOW leaves, `windows = {leaf: position leaf}`: a
bounded window `[rows, positions, width]` a row. The step gathers the
batch's rows of it for reading, and `step_score` returns for it not the
rows but ONE `[B, width]` entry, which the ring writes at `(row, the
row's own position)` into the donated buffer: a 1 MB context is read
whole, as attention must, and 1 KB of it is written. Such a model's
`step_score` also takes `live` (the rows that are no padding) and may
return a third value, the numbers of `model.step_stats`, which ride
home at the end of the score vector; the session feeds them to the
metrics the model declares (`stat_feeds`, models/seqblocks.py) without
knowing their names. Each window leaf has its own
bound, its `shape[1]`. One that the model names in `wraps` keeps the
newest `shape[1]` positions: the entry of position `p` is written at
`p mod shape[1]` over the oldest, so it never fills. The others are
bounded: a row is FULL when its position reaches the smallest of their
bounds, and a full row is seeded again before its next event, exactly
as at warm-up, from the last `window` values the ring was given for it
(its own host record of them: what the host store holds for the row
once it has taken the same events, without a race against the
persister). `model.cfg.window` may be longer than a wrapping leaf:
`warm_state` then leaves in it what the steps would have left, the
last `shape[1]` positions in their wrapped slots. `model.seed_rows`,
where declared, is how many rows one seeding call takes (a prefill's
activations have to fit beside the weights).

Such a model's FIXED-SIZE leaves of three or more dimensions (a row
that is a matrix: a recurrent state of 2.2 MB a layer, rewritten whole
at every event) reach `step_score` not as gathered rows but IN TURN, as
a `RowsInTurn` each: `read(after)` gathers the rows once `after` has
been computed, `write(rows, then)` puts their next values into the
donated table and returns `then`, and the model returns nothing for the
leaf. A layer reads its rows when it starts and writes them before the
next one starts. `update(fn, after)` is both at once for a model that
can update the rows WHERE THEY REST: once `after` has been computed it
calls `fn(table, dev)` with the whole table and the step's row indices
(padding included, past the scratch row), takes `(table, *outs)` back,
keeps that table as the leaf's next value and returns `outs`, which are
there once the table is written. It promises the order, `fn` promises
the ring's own contract: rows not named and the scratch row come back as
they were, and every write has landed when `fn`'s result is read
(models/olmo_hybrid.py hands it ops/state_kernel.py's kernel on a TPU:
2.2 MB rows cross HBM twice where gather, cell and scatter moved them
six times; PERF.md section 6, PR 36). Handed all rows at once, as the
other leaves are, a v5e's compiler gathered every layer's rows when the
step started and kept every layer's new rows until it ended: 4.05 GB of
scratch where one layer's at a time are 2.01 (three layers of 566 MB a
frame; PERF.md section 6, PR 35). `ring.rewritten_bytes` counts what the dispatches
since it was last read rewrote whole: live rows times the bytes of a
row of the leaves that are no window.

A WINDOW leaf that its model can read where it rests (the model names
it in `at_rest`) reaches `step_score` as a `ContextAtRest`, the window
leaves' sibling of `RowsInTurn`: it holds the table, the step's row
indices and the slot each row's entry goes to (the position, or the
position `mod shape[1]` where the leaf wraps). `append(entry)` writes
the position's own entry into the donated table, as `ctx_append` does
for the other window leaves when the step ends, and hands back the table
as it then rests; `rows()` is the gather of the other path. The model
appends in the layer's turn and reads the table behind the append (a
data dependence: models/seqblocks.py hands it to ops/context_kernel.py's
kernel on a TPU, which reads each row once where XLA's gather, the
decode form's own write and its second reading moved a row of megabytes
three times; PERF.md section 6, PR 38), returns nothing for the leaf,
and the ring takes the handle's table as the leaf's next value. What
reads the table promises to write nothing. A leaf whose row holds
several contexts side by side in blocks of lanes (one for each pass and
layer of a looped model, models/ouro.py) is gathered a block at a time,
`rows(block, width)`, and its model appends a position's whole line of
blocks once, when the step's last block is known.

The host `TelemetryStore` stays the durable copy; `load()` rebuilds
state from it at warmup or after a fault (same recovery story as the
window ring).
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.utils import grow_pow2
from sitewhere_tpu.utils.backend import device_memory_bytes

# how every scatter of the ring writes: padding lies past the table and
# is dropped, and no row is named twice ("Contract with the engines")
DISTINCT_ROWS = dict(mode="drop", unique_indices=True)


def pad_rows(scratch: int, n: int) -> np.ndarray:
    """`n` padding indices for a table whose scratch row is `scratch`:
    past the last row, ascending, one each."""
    return np.arange(scratch + 1, scratch + 1 + n, dtype=np.int32)


def table_rows(model, asked: int, floor: int) -> int:
    """Rows of a ring's table for a fleet of `asked` devices: the next
    power of two from `floor` up (room to grow into, few compiled
    shapes), unless a table that long would take over half of the
    device's memory by what `model.init_state`'s rows weigh (a context
    of megabytes a device); then as many rows as were asked for."""
    rows = grow_pow2(asked, floor=floor)
    limit = device_memory_bytes()
    if limit is None or rows == asked:
        return rows
    row = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(lambda: model.init_state(1))))
    return asked if rows * row > limit // 2 else rows


# the most a row may weigh for the compiler of a v5e to gather it as one
# slice: past 512 KiB it first slices the WHOLE table by lanes, then
# walks the rows of each piece in a loop (PERF.md section 6, PR 32)
GATHER_SLICE_BYTES = 1 << 19


def _rows(leaf, dev):
    """Rows `dev` of a table for reading (padding reads the scratch
    row). A row of three or more dimensions that is heavier than one
    gathered slice may be (a context `[positions, width]`, a matrix
    state `[groups, keys, lanes]`) is taken in blocks of its leading
    dimension, through a view of the table that splits it (no bytes
    move for a view): one gather either way."""
    row_bytes = leaf[0].size * leaf.dtype.itemsize
    if leaf.ndim < 3 or row_bytes <= GATHER_SLICE_BYTES:
        return leaf.at[dev].get(mode="clip", indices_are_sorted=True)
    rows, positions, *width = leaf.shape
    blocks = next(n for n in range(-(-row_bytes // GATHER_SLICE_BYTES),
                                   positions + 1) if positions % n == 0)
    got = leaf.reshape(rows, blocks, positions // blocks, *width).at[
        dev[:, None], jnp.arange(blocks)[None, :]].get(
            mode="clip", indices_are_sorted=True)
    return got.reshape(dev.shape[0], positions, *width)


def _slot(leaf, pos, wraps: bool):
    """Where a window leaf takes the entry of position `pos`: a leaf
    that wraps keeps the newest positions, in a circle."""
    return pos % leaf.shape[1] if wraps else pos


class RowsInTurn:
    """The rows of one fixed-size leaf whose row is a matrix, handed to
    `step_score` in the table's place ("Contract with the model"): the
    gather and the scatter are the ring's, WHEN they run is the
    model's."""

    def __init__(self, table, dev):
        self.table, self._dev = table, dev

    def read(self, after):
        """Rows `dev`, gathered once `after` has been computed."""
        dev, _ = jax.lax.optimization_barrier((self._dev, after))
        with jax.named_scope("ring_gather"):
            return _rows(self.table, dev)

    def write(self, rows, then):
        """The rows' next values into the table. -> `then`, which is
        there once they are in: read on from what is returned."""
        with jax.named_scope("ring_scatter"):
            table = self.table.at[self._dev].set(rows, **DISTINCT_ROWS)
        self.table, then = jax.lax.optimization_barrier((table, then))
        return then

    def update(self, fn, after):
        """The rows updated where they rest: `fn(table, dev)`, called
        once `after` has been computed, hands back `(table, *outs)`; the
        table is kept, and `outs` are there once it is written."""
        dev, _ = jax.lax.optimization_barrier((self._dev, after))
        table, *outs = fn(self.table, dev)
        self.table, outs = jax.lax.optimization_barrier((table, outs))
        return outs


class ContextAtRest:
    """One window leaf that its model reads where it rests, handed to
    `step_score` in the gathered rows' place ("Contract with the
    model"): the append is the ring's, WHEN it runs and what reads the
    table behind it are the model's. `read_rows` and `read_positions`
    are what the model's last reading took where they rested: live
    rows and the positions copied for them, 0 from the plain path."""

    def __init__(self, table, dev, slot):
        self.table, self.dev, self.slot = table, dev, slot
        self.read_rows = self.read_positions = 0

    def rows(self, block=None, width=None):
        """Rows `dev` gathered out of the table as it rests; of a table
        whose row holds contexts of `width` lanes side by side, the
        lanes of block `block` (an int32 scalar, traced or not)."""
        with jax.named_scope("ring_gather"):
            rows = _rows(self.table, self.dev)
            if block is None:
                return rows
            return jax.lax.dynamic_slice_in_dim(rows, block * width, width,
                                                axis=2)

    def append(self, entry):
        """The position's own `entry` `[B, width]` at `(row, slot)`, in
        place. -> the table as it then rests."""
        with jax.named_scope("ctx_append"):
            self.table = self.table.at[self.dev, self.slot].set(
                entry, **DISTINCT_ROWS)
        return self.table


def _gather_step_scatter(model, params, state, dev, v, scratch=None):
    """The ring step's three parts, under the `jax.named_scope`s a
    profile shows them by: rows of `dev` out of the table, one cell step
    on them, the new rows back (padding reads the scratch row and writes
    nothing). A window leaf takes one entry a row, at the row's own
    position; a leaf whose row is a matrix is read and written inside
    the cell step, in its turn. -> (state, scores, the step's numbers
    or None)."""
    windows = getattr(model, "windows", None)
    wraps = getattr(model, "wraps", ())
    at_rest = getattr(model, "at_rest", ())
    with jax.named_scope("ring_gather"):
        if windows is None:
            rows = jax.tree.map(lambda leaf: _rows(leaf, dev), state)
        else:
            rows = {name: RowsInTurn(leaf, dev) if leaf.ndim >= 3
                    and name not in windows else _rows(leaf, dev)
                    for name, leaf in state.items() if name not in at_rest}
            for name in at_rest:
                rows[name] = ContextAtRest(
                    state[name], dev, _slot(state[name], rows[windows[name]],
                                            name in wraps))
    stats = None
    with jax.named_scope("cell_step"):
        if windows is None:
            scores, new_rows = model.step_score(params, rows, v)
        else:
            scores, new_rows, stats = model.step_score(
                params, rows, v, live=dev < scratch)
    if windows is None:
        with jax.named_scope("ring_scatter"):
            state = jax.tree.map(
                lambda leaf, rows_new: leaf.at[dev].set(rows_new,
                                                        **DISTINCT_ROWS),
                state, new_rows)
        return state, scores, stats
    out = {name: rows[name].table for name in at_rest}
    with jax.named_scope("ctx_append"):
        for name, at in windows.items():
            if name not in at_rest:
                out[name] = state[name].at[
                    dev, _slot(state[name], rows[at], name in wraps)].set(
                        new_rows[name], **DISTINCT_ROWS)
    with jax.named_scope("ring_scatter"):
        for name, leaf in state.items():
            if isinstance(rows[name], RowsInTurn):
                out[name] = rows[name].table
            elif name not in windows:
                out[name] = leaf.at[dev].set(new_rows[name],
                                             **DISTINCT_ROWS)
    return out, scores, stats


def streaming_step(model, out_dtype=None) -> Callable:
    """The fused gather→step_score→scatter step body, shared by the
    dedicated ring (jit) and the stacked ring (jit∘vmap) so the two hot
    paths cannot diverge. The inner function keeps the name `step`: the
    benchmark's trace reduction finds its runs as module `jit_step`.

    `out_dtype` narrows the returned scores at the jit boundary (model
    state stays float32): float16 scores halve the only per-event
    payload the hot path ships back (whether readback bytes bound
    anything on the chip's own host: not measured, ROADMAP S4). Settle
    upcasts on assignment into its float32 result array."""

    def step(params, state, dev, v):
        # the scratch row is the table's last: padding lies past it
        scratch = jax.tree.leaves(state)[0].shape[0] - 1
        state, scores, stats = _gather_step_scatter(model, params, state,
                                                    dev, v, scratch)
        if out_dtype is not None:
            scores = scores.astype(out_dtype)
        if stats is not None:
            scores = jnp.concatenate([scores, stats.astype(scores.dtype)])
        return state, scores

    return step


def streaming_step_sparse(model, k: int,
                          scratch_index: int, out_dtype=None) -> Callable:
    """`streaming_step` with DEVICE-SIDE thresholding: every event is
    still scored and state-advanced on chip, but only the anomalous
    (position, score) pairs cross back to the host — decisions ride the
    wire, not bulk scores.

    What it does: shipping only anomalies shrinks the per-flush D2H
    payload from `bucket × 2 B` to `k × 6 B + 4` (k ≈ bucket/64), ~20×
    less. Whether full readback is a ceiling on the chip's own host is
    not measured (ROADMAP S4 decides whether this mode keeps a
    workload).

    Returns (n_anom, positions[k], scores[k]): `n_anom` counts real
    anomalies (scratch-row padding masked on device); positions index
    into the flush's padded bucket, sorted score-descending; entries
    past `min(n_anom, k)` are padding. `n_anom > k` means overflow —
    the host counts it (`scoring.anomaly_overflow`) so a silent top-k
    truncation is impossible.

    `threshold` is a RUNTIME argument (scalar here; the stacked ring
    vmaps it into a per-tenant vector — pooled tenants each set their
    own alert bar) so threshold changes never recompile."""

    def step(params, state, dev, v, threshold):
        state, scores, _ = _gather_step_scatter(model, params, state, dev,
                                                v, scratch_index)
        with jax.named_scope("sparse_topk"):
            # padding must never report: it reads the scratch row, whose
            # score means nothing
            is_anom = (scores >= threshold) & (dev < scratch_index)
            n_anom = is_anom.sum().astype(jnp.int32)
            masked = jnp.where(is_anom, scores, -jnp.inf)
            top_scores, top_pos = jax.lax.top_k(masked, k)
            if out_dtype is not None:
                top_scores = top_scores.astype(out_dtype)
        return state, (n_anom, top_pos.astype(jnp.int32), top_scores)

    return step


def result_ready(out) -> bool:
    """Device-result readiness for plain score arrays AND the sparse
    readback tuples — the single place that knows the tuple shape."""
    if isinstance(out, tuple):
        return all(a.is_ready() for a in out)
    return out.is_ready()


def result_to_host(out):
    """Settle-thread conversion for plain arrays AND sparse tuples."""
    if isinstance(out, tuple):
        return tuple(np.asarray(x) for x in out)
    return np.asarray(out)


def sparse_take(n_anom, pos, vals,
                n_real: int) -> tuple[np.ndarray, np.ndarray, int]:
    """Host-side reconstruction for ONE sparse result row: clamp to the
    k slots, drop bucket-padding positions (>= n_real — device-side
    scratch masking makes this belt-and-braces), upcast scores.
    Returns (positions, scores_f32, overflow). Called for every settled
    round of either engine (scoring/settle.py `anomalous_subset`)."""
    k_eff = min(int(n_anom), pos.shape[0])
    overflow = max(0, int(n_anom) - pos.shape[0])
    if k_eff == 0:
        return (np.empty(0, pos.dtype), np.empty(0, np.float32), overflow)
    p = pos[:k_eff]
    keep = p < n_real
    return p[keep], vals[:k_eff][keep].astype(np.float32), overflow


class StreamingRing:
    """Per-device streaming model state for up to `capacity` devices,
    plus one scratch row (index `capacity`) that padding reads."""

    def __init__(self, model, capacity: int = 1024,
                 initial_floor: int = 1024, score_dtype=None,
                 sparse_threshold: Optional[float] = None,
                 sparse_k: int = 0):
        self.model = model
        self.window = int(model.cfg.window)  # load()-contract width
        self.capacity = table_rows(model, int(capacity), initial_floor)
        self.score_dtype = jnp.dtype(score_dtype) if score_dtype else None
        # sparse anomaly readback (streaming_step_sparse): set a
        # threshold to ship only anomalous (position, score) pairs home
        self.sparse_threshold = sparse_threshold
        self.sparse_k = sparse_k
        self._fns: dict[tuple, Callable] = {}
        # jitted: an eager `lax.scan` recompiles on EVERY call (its step
        # closure is new each time), which put seconds of compile on the
        # event loop at each reload and each hot-swap on the chip
        self._warm_state = jax.jit(model.warm_state)
        # seeded rows into the donated table, any ascending rows: the
        # table is never copied for a block of them
        self._put = jax.jit(
            lambda state, seeded, rows: jax.tree.map(
                lambda leaf, new: leaf.at[rows].set(new, **DISTINCT_ROWS),
                state, seeded), donate_argnums=(0,))
        self.faulted = False
        self.state = jax.device_put(model.init_state(self.capacity + 1))
        # a model with window leaves: how many positions a row's bounded
        # windows hold (the smallest bound among those that do not wrap),
        # and on the host how many each row has filled (the device's
        # `pos` leaf, mirrored so that a full row is known without a
        # read-back) and the last `window` values it was given (a ring of
        # them a row, `_oldest` where the oldest lies): what a full row is
        # seeded again from. `reseeded` counts such rows.
        windows = getattr(model, "windows", None) or {}
        self._positions = min(
            (self.state[name].shape[1] for name in windows
             if name not in getattr(model, "wraps", ())), default=0)
        rows = self.capacity + 1 if windows else 0
        self._filled = np.zeros(rows, np.int32)
        self._recent = np.zeros((rows, self.window), np.float32)
        self._oldest = np.zeros(rows, np.int32)
        self.reseeded = 0
        # bytes of a row of the leaves that are no window (a step rewrites
        # them whole), and what the dispatches so far rewrote: the
        # session's to read and clear
        fixed = ([x for name, x in self.state.items() if name not in windows]
                 if windows else jax.tree.leaves(self.state))
        self.row_bytes = sum(x.dtype.itemsize * math.prod(x.shape[1:])
                             for x in fixed)
        self.rewritten_bytes = 0

    def ensure_capacity(self, max_index: int) -> None:
        if max_index < self.capacity:
            return
        new_cap = grow_pow2(max_index + 1, floor=self.capacity * 2)
        grow = new_cap - self.capacity
        fresh = self.model.init_state(grow + 1)

        def extend(leaf, pad):
            return jnp.concatenate([leaf[:-1], pad], axis=0)

        self.state = jax.tree.map(extend, self.state, fresh)
        if self._positions:
            def more(a):
                return np.concatenate(
                    [a[:-1], np.zeros((grow + 1,) + a.shape[1:], a.dtype)])

            self._filled, self._recent, self._oldest = (
                more(self._filled), more(self._recent), more(self._oldest))
        self.capacity = new_cap

    def load(self, values: np.ndarray, count: np.ndarray,
             rows: Optional[np.ndarray] = None) -> None:
        """Seed `rows` (strictly ascending; the first `n` where none are
        named) by replaying host windows (`TelemetryStore.window`
        layout: chronological, left-padded), `model.seed_rows` of them a
        call where the model says how many its seeding can take at
        once."""
        n, w = values.shape
        assert w == self.window
        if rows is None:
            rows = np.arange(n, dtype=np.int32)
        self.ensure_capacity(int(rows.max()) if n else 0)
        if n == 0:
            self.faulted = False
            return
        valid = np.arange(w)[None, :] >= (w - np.minimum(count, w))[:, None]
        params = getattr(self, "_params", None)
        if params is None:
            raise RuntimeError("StreamingRing.load needs params bound via "
                               "bind_params() before seeding")
        block = getattr(self.model, "seed_rows", None) or n
        for lo in range(0, n, block):
            x, ok, at = (values[lo:lo + block], valid[lo:lo + block],
                         rows[lo:lo + block])
            short = block - x.shape[0]
            if short:            # one compiled shape; padding is dropped
                x = np.concatenate([x, np.zeros((short, w), x.dtype)])
                ok = np.concatenate([ok, np.zeros((short, w), bool)])
                at = np.concatenate([at, pad_rows(self.capacity, short)])
            seeded = self._warm_state(params, jnp.asarray(x, jnp.float32),
                                      jnp.asarray(ok))
            self.state = self._put(self.state, seeded,
                                   jnp.asarray(at, jnp.int32))
            # one block's seeded rows at a time: a call's outputs are
            # allocated when it is dispatched, and dispatch ran 14 blocks
            # ahead of a v5e (3.7 GB of seeded rows alive at once beside
            # a table of 9.8 GB; PERF.md section 6, PR 35)
            jax.block_until_ready(seeded)
        if self._positions:
            self._filled[rows] = np.minimum(count, w)
            self._recent[rows], self._oldest[rows] = values, 0
        self.faulted = False

    def _reseed_full(self, dev: np.ndarray) -> None:
        """Rows of `dev` whose windows have no position left start again
        from their last `window` values, before this event of theirs."""
        full = dev[self._filled[dev] >= self._positions]
        if full.size == 0:
            return
        order = (self._oldest[full, None] + np.arange(self.window)) \
            % self.window
        self.load(np.take_along_axis(self._recent[full], order, axis=1),
                  np.full(full.size, self.window), rows=full)
        self.reseeded += int(full.size)

    def bind_params(self, params: dict) -> None:
        """Streaming state depends on the weights (h/c/pred are functions
        of them): the session binds current params before load()."""
        self._params = params

    # -- compiled step -----------------------------------------------------

    def _build_step(self, cap: int, bucket: int) -> Callable:
        if self.sparse_threshold is not None:
            k = self.sparse_k or max(128, bucket // 64)
            return jax.jit(streaming_step_sparse(
                self.model, min(k, bucket),
                scratch_index=cap, out_dtype=self.score_dtype),
                donate_argnums=(1,))
        return jax.jit(streaming_step(self.model, self.score_dtype),
                       donate_argnums=(1,))

    def _pad(self, dev: np.ndarray, v: np.ndarray,
             bucket: int) -> tuple[np.ndarray, np.ndarray]:
        n = dev.shape[0]
        out_dev = np.empty(bucket, np.int32)
        out_v = np.zeros(bucket, np.float32)
        out_dev[:n] = dev
        out_dev[n:] = pad_rows(self.capacity, bucket - n)
        out_v[:n] = v
        return out_dev, out_v

    def update_and_score(self, model, params, dev: np.ndarray,
                         v: np.ndarray, bucket: int) -> jax.Array:
        """Advance + score one event per row of `dev` (strictly
        ascending ids!); returns `[bucket]` scores on device (async)."""
        self._params = params
        if self._positions and dev.size:
            self._reseed_full(dev)
        key = (self.capacity, bucket)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._build_step(self.capacity, bucket)
        pdev, pv = self._pad(dev, v, bucket)
        try:
            if self.sparse_threshold is not None:
                self.state, scores = fn(
                    params, self.state, pdev, pv,
                    np.float32(self.sparse_threshold))
            else:
                self.state, scores = fn(params, self.state, pdev, pv)
        except Exception:
            self.faulted = True  # donated state is gone; needs load()
            raise
        self.rewritten_bytes += int(dev.size) * self.row_bytes
        if self._positions:
            self._filled[dev] += 1
            at = self._oldest[dev]
            self._recent[dev, at] = v
            self._oldest[dev] = (at + 1) % self.window
        return scores

    def close(self) -> None:
        """Compiled steps and the table go: a closed ring holds nothing
        of the device."""
        self._fns.clear()
        self.state = self._params = None


class StackedStreamingRing:
    """Per-tenant streaming model state stacked on a leading tenant axis
    — the pooled (config 4) twin of `StreamingRing`, and the streaming
    twin of `ring.StackedDeviceRing`.

    State leaves are `[T_cap, D_cap+1, ...]`; with a mesh the tenant
    axis is sharded over `model` (matching the stacked params in
    parallel/tenant_stack.py), so each device holds its tenants' model
    state resident. One flush is ONE jitted

        vmap(gather rows → model.step_score → scatter back)

    over the tenant axis, donated in place: every tenant's events cost
    one cell step each (not a W-step window rescan), uploading only the
    `[T_cap, B]` (device id, value) deltas. Padding reads each
    tenant's scratch row `D_cap` and writes nothing.

    Seeding is per-tenant (`load_tenant`) because streaming state is a
    function of that tenant's WEIGHTS — the caller passes the tenant's
    unstacked params and the state is rebuilt by `model.warm_state`
    replay of its host windows (same recovery story as the other rings).
    """

    def __init__(self, model, n_tenants: int, device_cap: int = 1024,
                 mesh=None, score_dtype=None, sparse: bool = False,
                 sparse_k: int = 0):
        from sitewhere_tpu.parallel.mesh import (
            megabatch_placer,
            tenant_placer,
        )

        self.model = model
        self.window = int(model.cfg.window)
        self.mesh = mesh
        self.score_dtype = jnp.dtype(score_dtype) if score_dtype else None
        # sparse anomaly readback, pooled form: per-tenant thresholds
        # ride as a [T_cap] runtime vector (each tenant sets its own
        # alert bar at register())
        self.sparse = sparse
        self.sparse_k = sparse_k
        self.t_cap = int(n_tenants)
        self.device_cap = grow_pow2(int(device_cap), floor=1024)
        self._fns: dict[tuple, Callable] = {}
        self._warm_state = jax.jit(model.warm_state)  # see StreamingRing
        self.faulted = False
        self._place = tenant_placer(mesh)
        # [T_cap, B] dispatch deltas shard tenant→model, batch→data —
        # the same serving-mesh convention as the stacked window ring
        self._place_in = megabatch_placer(mesh)
        self.state = self._alloc(self.t_cap, self.device_cap)

    def _alloc(self, t: int, d: int):
        single = self.model.init_state(d + 1)  # leaves [d+1, ...]
        return jax.tree.map(
            lambda leaf: self._place(
                jnp.tile(leaf[None], (t,) + (1,) * leaf.ndim)),
            single)

    # -- capacity ----------------------------------------------------------

    def ensure(self, n_tenants: int, max_device: int) -> None:
        """Grow either axis (device-side). The tenant axis adopts
        `n_tenants` exactly — it must equal the param stack's capacity
        (vmap needs matching leading dims)."""
        new_t = max(self.t_cap, n_tenants)
        new_d = self.device_cap
        if max_device >= new_d:
            new_d = grow_pow2(max_device + 1, floor=new_d * 2)
        if new_t == self.t_cap and new_d == self.device_cap:
            return
        if new_d != self.device_cap:
            # drop the old scratch row, append fresh rows + a fresh
            # scratch per tenant (fresh rows are weight-independent
            # zeros; real devices landing there get warm-seeded or
            # simply accumulate state from their next events)
            fresh = self.model.init_state(new_d - self.device_cap + 1)

            def extend_d(leaf, pad):
                pad_t = jnp.tile(pad[None], (self.t_cap,) + (1,) * pad.ndim)
                return jnp.concatenate([leaf[:, :-1], pad_t], axis=1)

            self.state = jax.tree.map(extend_d, self.state, fresh)
        if new_t != self.t_cap:
            grown = self._alloc(new_t - self.t_cap, new_d)
            self.state = jax.tree.map(
                lambda leaf, pad: jnp.concatenate([leaf, pad], axis=0),
                self.state, grown)
        self.state = jax.tree.map(self._place, self.state)
        self.t_cap, self.device_cap = new_t, new_d

    # -- seeding -----------------------------------------------------------

    def load_tenant(self, slot: int, values: np.ndarray, count: np.ndarray,
                    params: dict) -> None:
        """Seed one tenant's state rows by replaying its host windows
        (`TelemetryStore.window` layout) under ITS params."""
        n, w = values.shape
        assert w == self.window
        self.ensure(slot + 1, n - 1 if n else 0)
        if n == 0:
            self.faulted = False
            return
        valid = np.arange(w)[None, :] >= (w - np.minimum(count, w))[:, None]
        seeded = self._warm_state(
            params, jnp.asarray(values, jnp.float32), jnp.asarray(valid))

        def put(leaf, rows):
            return self._place(leaf.at[slot, :n].set(rows))

        self.state = jax.tree.map(put, self.state, seeded)
        self.faulted = False

    def clear_tenant(self, slot: int) -> None:
        """Reset a departed tenant's rows (slot reuse must not leak)."""
        fresh = self.model.init_state(self.device_cap + 1)
        self.state = jax.tree.map(
            lambda leaf, f: self._place(leaf.at[slot].set(f)),
            self.state, fresh)

    # -- compiled step -----------------------------------------------------

    def _build_step(self, bucket: int) -> Callable:
        if self.sparse:
            k = self.sparse_k or max(128, bucket // 64)
            return jax.jit(jax.vmap(streaming_step_sparse(
                self.model, min(k, bucket),
                scratch_index=self.device_cap,
                out_dtype=self.score_dtype)),
                donate_argnums=(1,))
        return jax.jit(jax.vmap(streaming_step(self.model, self.score_dtype)),
                       donate_argnums=(1,))

    def padding(self, bucket: int) -> np.ndarray:
        """What a tenant row of `bucket` slots holds where it has no
        event (`pad_rows`): a take's ids overwrite the head, the tail
        that is left still ascends past them."""
        return pad_rows(self.device_cap, bucket)

    def update_and_score(self, model, stacked_params, dev: np.ndarray,
                         v: np.ndarray, thresholds=None):
        """dev: [T_cap, B] int32 (each tenant row strictly ascending,
        its tail `pad_rows`), v: [T_cap, B] float32 → [T_cap, B] scores on
        device (async); sparse mode returns per-tenant
        (n_anom[T], positions[T, k], scores[T, k]) and needs
        `thresholds` [T_cap] float32."""
        key = ("ss", self.sparse, self.t_cap, self.device_cap,
               dev.shape[1])
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._build_step(dev.shape[1])
        try:
            if self.sparse:
                self.state, scores = fn(stacked_params, self.state,
                                        self._place_in(dev),
                                        self._place_in(v),
                                        jnp.asarray(thresholds,
                                                    jnp.float32))
            else:
                self.state, scores = fn(stacked_params, self.state,
                                        self._place_in(dev),
                                        self._place_in(v))
        except Exception:
            self.faulted = True  # donated state is gone; needs reseeding
            raise
        return scores

    def close(self) -> None:
        self._fns.clear()
