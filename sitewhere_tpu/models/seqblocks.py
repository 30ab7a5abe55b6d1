"""What the streaming sequence scorers have in common (`dsv3-stream`,
models/dsv3.py; `laguna-stream`, models/laguna.py; `olmo-hybrid-stream`,
models/olmo_hybrid.py; `lfm2-stream`, models/lfm2.py; `ouro-stream`,
models/ouro.py; `nemotron-h-stream`, models/nemotron_h.py): RMSNorm, rope in
its two pairings and its YaRN tables, the quantiser
that makes a measurement a token, the surprisal score with its
short-history gate, attention over stored keys and values in a prefill
and a decode form, and the expert layer that is told which experts it
holds, its experts SiLU-gated (three leaves) or `relu` squared (two).

A measurement becomes a token by the device's capped running mean and
variance (the leaves and the update `lstm-stream` has):
`bin = clip(floor((xn + 8) / 16 * V), 0, V - 1)`; an event's score is
the surprisal of the bin that arrived under the prediction made at the
device's previous event, 0 until the device has reported
`max(8, window // 8)` values, clipped at `score_clip`.

The share of an expert layer held here (`Experts`): the layer routes
over all `routed` experts, computes every token-expert pair that lands
on one of the `held` experts from `first` on (no capacity, none
dropped) and leaves out what the absent experts would add.

A model mixes `SeqBlocks` in, sets `self.cfg` (with `compute_dtype`,
`window`, `score_clip`, `vocab`, `hidden_size`, `rms_norm_eps`),
`self.experts` (an `Experts`, where it has an expert layer),
`self.seed_rows` and `self._gate`, `step_stats` and `stat_families`, and
brings `param_shapes`, `init_state` and `_prefill`. Imports nothing
beyond JAX at import time: the expert kernel imports Pallas when it is
first traced.
"""

from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from sitewhere_tpu.ops import context_kernel, expert_kernel

EXPERT_TILE = 128        # rows of one held expert's products at a time
SEED_TOKENS = 2048       # tokens of one seeding call: its activations
                         # (under 1 GB at the published widths) fit
                         # beside the weights and the context


@dataclass(frozen=True)
class Experts:
    """An expert layer's routing rule and the share of it held here."""
    routed: int              # experts the router scores
    held: int                # ...of which this chip holds `held`
    first: int               # ...from expert `first` on
    per_token: int
    scale: float             # the kept weights sum to this
    scoring: str             # "sigmoid" | "softmax" of the router's logits
    groups: int = 1          # group-limited choice: the experts in
    groups_kept: int = 1     # `groups`, of which the best are kept
    normed: bool = True      # the kept weights are divided by their sum
    sum_eps: float = 0.0     # ...plus this, where the published rule adds it


def held_expert_bytes(model) -> int:
    """Bytes of the held experts' leaves over a model's expert layers
    (`param_shapes()`: a layer's `experts`, a leaf a projection an
    expert), which a step streams once whatever a frame routes; 0 for a
    model without them."""
    shapes = getattr(model, "param_shapes", dict)()
    return sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for block in shapes.values() if isinstance(block, dict)
               for expert in block.get("experts", {}).values()
               for shape, dtype in expert.values())


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_tables(positions: int, dim: int, base: float,
                yarn: dict | None = None) -> tuple:
    """(cos, sin) `[positions, dim // 2]` for a rotated width of `dim`;
    with `yarn` (`factor`, `original_max_position_embeddings`,
    `beta_fast`, `beta_slow`) as published: frequencies above the
    correction range keep theta's, those below are divided by `factor`,
    a linear ramp between."""
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn:
        orig = yarn["original_max_position_embeddings"]

        def correction(rotations):
            return dim * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction(yarn["beta_fast"])), 0)
        high = min(math.ceil(correction(yarn["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low)
                       / ((high - low) or 0.001), 0.0, 1.0)
        freq = freq / yarn["factor"] * ramp + freq * (1.0 - ramp)
    angle = np.arange(positions, dtype=np.float64)[:, None] * freq[None, :]
    return (np.cos(angle).astype(np.float32),
            np.sin(angle).astype(np.float32))


def rope(x, cos, sin):
    """Rotate pairs `(2i, 2i + 1)` of the last axis; `cos`, `sin`
    broadcast against `x[..., ::2]`."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def rope_halves(x, cos, sin):
    """Rotate pairs `(i, i + d / 2)` of the last axis, `d` its width:
    the half-split pairing; `cos`, `sin` broadcast against `x[..., :d /
    2]`. The same turn as `rope`'s under a permutation of the columns
    of the projections that make `x`."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def runs_one_tile(counts, tile=EXPERT_TILE):
    """Of the held experts' runs `counts` `[held]`, those that
    `SeqBlocks.routed`'s straight-line pass serves whole (an empty run
    too); the others enter its overflow loop."""
    return (counts <= tile).sum()


OCTAVES = [2.0 ** (i / 4) for i in range(53)]      # 1 to 8,192


def precision(cdt):
    return (jax.lax.Precision.HIGHEST if jnp.dtype(cdt) == jnp.float32
            else None)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class SeqBlocks:
    """The pieces, as methods of the model that mixes them in."""

    # -- what a step says of itself: the numbers it returns beside the
    # scores (`step_stats`), each declared with its metric's full name,
    # kind and buckets by one of the families a model names
    # (`stat_families`); a family maps a number to what registers its
    # metric and returns the feed, and is called with the model

    step_stats: tuple = ()
    stat_families: tuple = ()

    def stat_feeds(self, metrics) -> tuple[list, list]:
        """The serving engine's feeds, registered on `metrics`: one a
        number of `step_stats`, in its order, and those called once a
        dispatch (the bytes of held experts' leaves a step streams)."""
        declared = {}
        for family in self.stat_families:
            declared.update(family(self, metrics))
        per_step = [declared[name]() for name in self.step_stats]
        held = held_expert_bytes(self)
        return per_step, [functools.partial(metrics.counter(
            "scoring.moe.weight_bytes").inc, held)] if held else []

    def expert_stats(self, metrics) -> dict:
        """An expert layer's (`Experts`): pairs held, pairs routed, the
        most tokens one held expert took, runs served in one tile."""
        return {
            "moe.assignments_held": lambda: metrics.counter(
                "scoring.moe.assignments_held").inc,
            "moe.assignments": lambda: metrics.counter(
                "scoring.moe.assignments").inc,
            "moe.expert_max_tokens": lambda: metrics.histogram(
                "scoring.moe.expert_max_tokens", buckets=OCTAVES).observe,
            "moe.runs_one_tile": lambda: metrics.counter(
                "scoring.moe.runs_one_tile").inc}

    def context_stats(self, metrics) -> dict:
        """Attention over stored contexts: the mean attended length over
        the bounded window leaves and over those that wrap, live rows
        whose append overwrote a wrapping leaf's oldest position, live
        rows whose context the step's kernel read where it rested and the
        positions it copied of each of their tables (0 on the plain
        path), bytes of keys and values a looped model read."""
        return {
            "ctx.positions": lambda: metrics.histogram(
                "scoring.ctx.positions", buckets=OCTAVES).observe,
            "ctx.window_positions": lambda: metrics.histogram(
                "scoring.ctx.window_positions", buckets=OCTAVES).observe,
            "ctx.wrapped": lambda: metrics.counter(
                "scoring.ctx.wrapped").inc,
            "ctx.at_rest": lambda: metrics.counter(
                "scoring.ctx.at_rest_rows").inc,
            "ctx.read_positions": lambda: metrics.counter(
                "scoring.ctx.read_positions").inc,
            "ctx.attended_bytes": lambda: metrics.counter(
                "scoring.ctx.attended_bytes").inc}

    def state_stats(self, metrics) -> dict:
        """A recurrent matrix state's (models/olmo_hybrid.py,
        models/nemotron_h.py): the mean decay a step applied, in (0, 1),
        the largest magnitude in the rows it read, and live rows whose
        state its kernel updated where it rested (0 on the plain path),
        each of which the kernel reads and writes whole: the bytes it
        must move, live rows x layers x the model's `state_row_bytes`
        (a layer's row) x 2, from shapes."""
        def in_place():
            rows = metrics.counter("scoring.state.in_place_rows")
            moved = metrics.counter("scoring.state.kernel_bytes")

            def feed(n):
                rows.inc(n)
                moved.inc(2 * self.state_row_bytes * n)

            return feed

        return {
            "state.decay": lambda: metrics.histogram(
                "scoring.state.decay",
                buckets=[i / 64 for i in range(1, 65)]).observe,
            "state.absmax": lambda: metrics.histogram(
                "scoring.state.absmax",
                buckets=[2.0 ** (i / 4) for i in range(-96, 33)]).observe,
            "state.in_place": in_place}

    def loop_stats(self, metrics) -> dict:
        """A looped model's (models/ouro.py): bytes of layer weights its
        passes stream a step, passes x layers x a layer's, from shapes."""
        return {"loop.weight_bytes": lambda: metrics.counter(
            "scoring.loop.weight_bytes").inc}

    def _mm(self, x, w):
        cdt = self.cfg.compute_dtype
        return jnp.dot(x.astype(cdt), w.astype(cdt),
                       preferred_element_type=jnp.float32,
                       precision=precision(cdt))

    def _ein(self, spec, a, b):
        cdt = self.cfg.compute_dtype
        return jnp.einsum(spec, a.astype(cdt), b.astype(cdt),
                          preferred_element_type=jnp.float32,
                          precision=precision(cdt))

    def _mlp(self, p, x):
        """A SiLU-gated MLP (`gate`, `up`, `down`), or where it has no
        `gate` the ungated `relu(x up)^2 down`."""
        if "gate" not in p:
            return self._mm(jnp.square(jax.nn.relu(self._mm(x, p["up"]))),
                            p["down"])
        return self._mm(jax.nn.silu(self._mm(x, p["gate"]))
                        * self._mm(x, p["up"]), p["down"])

    def init(self, rng: jax.Array) -> dict:
        """Random weights leaf by leaf over `param_shapes()` (normal, std
        0.02; norms 1; a router's bias std 0.01), each made in float32
        and kept in its own type, so that no second copy of the set is
        ever alive."""
        made = itertools.count()

        def build(spec, name=""):
            if isinstance(spec, dict):
                return {k: build(v, k) for k, v in spec.items()}
            shape, dtype = spec
            if "norm" in name:
                return jnp.ones(shape, dtype)
            return normal(jax.random.fold_in(rng, next(made)), shape, dtype,
                          0.01 if name == "bias" else 0.02)

        return build(self.param_shapes())

    # -- the expert layer ---------------------------------------------------

    def route(self, p, x):
        """Experts and weights of tokens `x` `[T, hidden]` (float32):
        (`[T, k]` int32, `[T, k]` float32), over ALL routed experts.
        `s` = the router's scoring function of `x W^T`; choice scores
        `s` plus the router's bias where it has one; with groups, a
        group's score is the sum of its two best and only the kept
        groups' experts can be chosen; weights the chosen `s` over their
        sum (plus `sum_eps`; as they are where not `normed`) times
        `scale`."""
        ex = self.experts
        logits = jnp.dot(x.astype(jnp.float32), p["w"].T,
                         precision=jax.lax.Precision.HIGHEST)
        s = (jax.nn.sigmoid(logits) if ex.scoring == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        choice = s + p["bias"] if "bias" in p else s
        if ex.groups > 1:
            groups = choice.reshape(x.shape[0], ex.groups, -1)
            group_score = jax.lax.top_k(groups, 2)[0].sum(-1)
            kept = jax.lax.top_k(group_score, ex.groups_kept)[1]
            keep = jnp.zeros((x.shape[0], ex.groups), bool).at[
                jnp.arange(x.shape[0])[:, None], kept].set(True)
            choice = jnp.where(jnp.repeat(keep, groups.shape[-1], axis=1),
                               choice, -jnp.inf)
        idx = jax.lax.top_k(choice, ex.per_token)[1]
        w = jnp.take_along_axis(s, idx, axis=1)
        if not ex.normed:
            return idx.astype(jnp.int32), w * ex.scale
        total = w.sum(-1, keepdims=True)
        if ex.sum_eps:
            total = total + ex.sum_eps
        w = w / total * ex.scale
        return idx.astype(jnp.int32), w

    def routed(self, p, x, idx, w, live, tile=EXPERT_TILE):
        """What the held experts give for tokens `x` `[T, hidden]`:
        `sum_k w * expert_k(x)` over the chosen experts held here, and
        each held expert's token count `[held]` (rows not `live` count
        and compute nothing). The pairs are sorted by expert and the
        layer is ONE grouped pass over them: the token rows of every
        held expert's first `tile` pairs are gathered once, laid at
        `e * tile`, each expert's three products run over its tile with
        no loop round them (an expert's leaves are read once, one after
        another), and the weighted rows are summed into the tokens.
        Runs longer than `tile` take their further tiles in further
        passes of the same kind, tile `n` of every held expert at once
        (an expert whose run has ended adds nothing), in ONE loop that
        runs while some run goes on: nothing is dropped, no expert has
        a capacity. (One loop an expert under a branch, 64 a layer, was
        four fifths of a step's compile: PERF.md section 6, PR 39.)"""
        c = self.cfg
        t, k = idx.shape
        held = self.experts.held
        local = idx.reshape(-1) - self.experts.first
        here = (local >= 0) & (local < held) & jnp.repeat(live, k)
        group = jnp.where(here, local, held)
        _, token, weight = jax.lax.sort(
            (group, jnp.arange(t * k, dtype=jnp.int32) // k, w.reshape(-1)),
            num_keys=1, is_stable=True)
        counts = (group[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)
        starts = jnp.cumsum(counts) - counts
        token = jnp.concatenate([token, jnp.zeros(tile, jnp.int32)])
        weight = jnp.concatenate([weight, jnp.zeros(tile, jnp.float32)])
        xc = x.astype(c.compute_dtype)
        lane = jnp.arange(tile)
        experts = [p[f"e{e}"] for e in range(held)]

        def tiles(n, left):
            """`_first_tiles` over tile `n` of every run, of which
            `left` `[held]` pairs are real: the pairs' token rows and
            their weights, 0 from a run's end on (an ended run's tile
            lies in the padding)."""
            at = jnp.minimum(starts + n * tile, t * k)[:, None] + lane
            wt = jnp.where(at < (starts + counts)[:, None], weight[at], 0.0)
            return self._first_tiles(experts, xc, token[at].reshape(-1),
                                     wt.reshape(-1), left)

        def further(at):
            n, out = at
            return n + 1, out + tiles(n, jnp.clip(counts - n * tile, 0, tile))

        _, out = jax.lax.while_loop(
            lambda at: (counts > at[0] * tile).any(), further,
            (jnp.int32(1), tiles(0, counts)))
        return out, counts

    def _first_tiles(self, experts, xc, rows, wt, counts):
        """`sum w * expert(x)` over one tile of pairs a held expert (its
        first, or a further one): `rows`, `wt` `[held * tile]` are the
        pairs' tokens and weights (0 past a run's end), tile `e` expert
        `e`'s, `counts` `[held]` how many of a tile's pairs are real. ->
        `[T, hidden]` float32. On a TPU, in bfloat16 and at shapes it
        takes, one kernel that streams the leaves where they rest and
        sums in place (ops/expert_kernel.py); elsewhere the same three
        products and a scatter-add an expert."""
        t, tile = xc.shape[0], rows.shape[0] // len(experts)

        def plain(experts, xs, rows, wt, counts):
            # a scatter-add an expert: one of every tile's rows at once
            # took twice their time on a v5e (PERF.md, PR 29); `counts`
            # is for the kernel, here `wt` is 0 past a run's end
            out = jnp.zeros((t, xs.shape[1]), jnp.float32)
            for e, expert in enumerate(experts):
                at = slice(e * tile, (e + 1) * tile)
                out = out.at[rows[at]].add(
                    self._mlp(expert, xs[at]) * wt[at, None])
            return out

        hidden, inter = experts[0]["up"].shape
        if (jnp.dtype(self.cfg.compute_dtype) != jnp.bfloat16
                or not expert_kernel.fits(t, hidden, inter, tile,
                                          len(experts[0]))):
            return plain(experts, xc[rows], rows, wt, counts)
        return jax.lax.platform_dependent(
            experts, xc[rows], rows, wt, counts, default=plain,
            tpu=functools.partial(expert_kernel.expert_tiles, tokens=t))

    def _ffn(self, p, x, live):
        """The block's second half on normed tokens `[T, hidden]`; the
        held experts' token counts `[held]` where the layer has experts
        (a shared expert is added where the layer has one)."""
        if "mlp" in p:
            with jax.named_scope("dense_mlp"):
                return self._mlp(p["mlp"], x), None
        with jax.named_scope("moe_route"):
            idx, w = self.route(p["router"], x)
        with jax.named_scope("moe_experts"):
            routed, counts = self._routed(p["experts"], x, idx, w, live)
            if "shared" not in p:
                return routed, counts
            return self._mlp(p["shared"], x) + routed, counts

    # -- attention over a row's stored keys and values ---------------------------

    def _causal_prefill(self, q, k, v, count, kv: int, band=None):
        """The prefill form over `[n, S]` tokens: causal softmax, over
        the newest `band` positions only where a band is given,
        positions at or past a row's `count` masked out. `q` `[n, S,
        heads, d]`; `k`, `v` `[n, S, kv * d]` as stored, query head `h`
        reading key-value head `h // (heads / kv)`. The model sets
        `self._scale`. -> `[n, S, heads, d]`."""
        n, s, heads, d = q.shape
        logits = self._ein(
            "nqkgd,nskd->nkgqs", q.reshape(n, s, kv, heads // kv, d),
            k.reshape(n, s, kv, d)) * self._scale
        at = jnp.arange(s)
        seen = at[None, :] <= at[:, None]
        if band is not None:
            seen &= at[:, None] - at[None, :] < band
        seen = seen[None] \
            & (at[None, None, :] < jnp.maximum(count, 1)[:, None, None])
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None], logits, -jnp.inf), axis=-1)
        out = self._ein("nkgqs,nskd->nqkgd", probs, v.reshape(n, s, kv, d))
        return out.reshape(n, s, heads, d)

    def _decode_rows(self, q, k, v, kctx, vctx, pos, kv: int,
                     wraps: bool = False):
        """The decode form for one token a row: `kctx`, `vctx` `[B, P,
        kv * d]` are the row's stored context, `k`, `v` `[B, kv * d]`
        its own position's, at `pos` (a context that `wraps`: at `pos
        mod P`, and once it has wrapped every slot is inside the
        window). The query of head `h` is laid in the lanes of its
        key-value head and zeros elsewhere, so the logits are one
        product over a position's whole entry, and a head's output is
        read back from the same lanes of the weighted sum of values:
        the context is read as it rests. -> `[B, heads, d]`."""
        b, heads, d = q.shape
        positions = kctx.shape[1]
        rows = jnp.arange(b)
        slot = pos % positions if wraps else pos
        keys = kctx.at[rows, slot].set(k, mode="drop")
        vals = vctx.at[rows, slot].set(v, mode="drop")
        own = jnp.eye(kv, dtype=jnp.float32)[None, :, None, :, None]
        wide = (q.reshape(b, kv, heads // kv, 1, d) * own).reshape(
            b, heads, kv * d)
        logits = self._ein("bhc,bpc->bhp", wide, keys) * self._scale
        seen = jnp.arange(positions)[None, :] <= pos[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, :], logits, -jnp.inf), axis=-1)
        out = self._ein("bhp,bpc->bhc", probs, vals)
        return (out.reshape(b, kv, heads // kv, kv, d) * own).sum(3).reshape(
            b, heads, d)

    def _decode_at_rest(self, q, k, v, kctx, vctx, pos, kv: int,
                        wraps: bool = False):
        """`_decode_rows` over a context that stays in the ring's table:
        `kctx`, `vctx` are the layer's two window leaves as the ring
        hands them over (scoring/stream.py, `ContextAtRest`). On a TPU,
        in bfloat16 and at shapes it takes (a key-value head of whole
        lane tiles, or of 64 lanes, two to a tile), the position's own
        entry is appended first and ONE kernel reads each row's keys and
        values where they then rest (ops/context_kernel.py): the same
        lines, no gathered copy. Elsewhere the rows are gathered, `_decode_rows`
        reads them and the entries are appended: one algorithm, and the
        plain path is the kernel's twin in the tests. `kctx.read_rows`
        and `kctx.read_positions` are left saying how many live rows
        were read at rest and how many positions of each table were
        copied for them (`context_kernel.reads`). -> `[B, heads, d]`."""
        dev, slot = kctx.dev, kctx.slot

        def handles(ktab, vtab):
            return type(kctx)(ktab, dev, slot), type(vctx)(vtab, dev, slot)

        def plain(ktab, vtab, q, k, v):
            keys, vals = handles(ktab, vtab)
            out = self._decode_rows(q, k, v, keys.rows(), vals.rows(), pos,
                                    kv, wraps)
            return (keys.append(k), vals.append(v), out, jnp.int32(0),
                    jnp.int32(0))

        def rested(ktab, vtab, q, k, v):
            keys, vals = handles(ktab, vtab)
            ktab, vtab = keys.append(k), vals.append(v)
            out = context_kernel.context_rows(ktab, vtab, dev, pos, q,
                                              kv=kv, scale=self._scale)
            return (ktab, vtab, out,
                    *context_kernel.reads(ktab.shape, dev, pos))

        args = (kctx.table, vctx.table, q, k, v)
        leaf = (kctx.table.shape, kctx.table.dtype, q.shape[1], kv)
        if (jnp.dtype(self.cfg.compute_dtype) != jnp.bfloat16
                or not (context_kernel.fits(*leaf)
                        or context_kernel.fits_paired(*leaf))):
            took = plain(*args)
        else:
            took = jax.lax.platform_dependent(*args, default=plain,
                                              tpu=rested)
        (kctx.table, vctx.table, out, kctx.read_rows,
         kctx.read_positions) = took
        return out

    # -- tokens and the score -------------------------------------------------

    def _bin(self, xn):
        v = self.cfg.vocab
        return jnp.clip(jnp.floor((xn + 8.0) / 16.0 * v), 0,
                        v - 1).astype(jnp.int32)

    def _window_tokens(self, x, valid):
        """A stored window `[n, W]` (chronological, left-padded) as
        tokens with the valid ones first, and the window's statistics:
        (tokens `[n, W]`, count `[n]`, mean `[n]`, var `[n]`). The
        statistics are taken value by value in stored order, by the rule
        an event updates them with: no sum, so no order of summation
        for another program to disagree about at a bin's edge."""
        n, w = x.shape

        def take(carry, col):
            mean, var, cnt = carry
            v, ok = col
            cnt1 = jnp.minimum(cnt + 1, w)
            d = v - mean
            mean1 = mean + d / cnt1
            var1 = var + ((v - mean1) * d - var) / cnt1
            return (jnp.where(ok, mean1, mean), jnp.where(ok, var1, var),
                    jnp.where(ok, cnt1, cnt)), None

        (mean, var, count), _ = jax.lax.scan(
            take, (jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
                   jnp.zeros(n, jnp.int32)), (x.T, valid.T))
        xn = (x - mean[:, None]) / jnp.sqrt(var + 1e-6)[:, None]
        first = (jnp.arange(w)[None, :] + (w - count)[:, None]) % w
        return (jnp.take_along_axis(self._bin(xn), first, axis=1), count,
                mean, var)

    def _arrive(self, params, rows, v):
        """An event's first half, from the row's scalars and `hn`: the
        token of the value that arrived, its score (the surprisal under
        the head's prediction at the previous event, gated and clipped)
        and the row's next `mean`, `var`, `count`, `pos`."""
        c = self.cfg
        mean, var, cnt, pos = (rows["mean"], rows["var"], rows["count"],
                               rows["pos"])
        token = self._bin((v - mean) / jnp.sqrt(var + 1e-6))
        with jax.named_scope("lm_head"):
            logits = self._mm(rows["hn"], self._head(params))
            surprisal = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, token[:, None], axis=1)[:, 0]
        score = jnp.clip(jnp.where(cnt >= self._gate, surprisal, 0.0),
                         0.0, c.score_clip)
        cnt1 = jnp.minimum(cnt + 1, c.window)
        delta = v - mean
        mean1 = mean + delta / cnt1
        var1 = var + ((v - mean1) * delta - var) / cnt1
        return token, score, {"mean": mean1, "var": var1, "count": cnt1,
                              "pos": pos + 1}

    def _row_state(self, cap: int) -> dict:
        """The leaves every such model keeps a row: the running
        statistics, the position, and what the head needs of the
        previous event (the final norm's output)."""
        c = self.cfg
        return {"mean": jnp.zeros(cap, jnp.float32),
                "var": jnp.ones(cap, jnp.float32),
                "count": jnp.zeros(cap, jnp.int32),
                "pos": jnp.zeros(cap, jnp.int32),
                "hn": jnp.zeros((cap, c.hidden_size), c.compute_dtype)}

    def _warm(self, params, x, valid):
        """Seeding's first half, the prefill form over stored windows `[n,
        W]` (chronological, left-padded): (the state of `n` rows with
        its window leaves still empty, the prefill's context entries a
        layer, the values a row holds `[n]`)."""
        c = self.cfg
        n = x.shape[0]
        tokens, count, mean, var = self._window_tokens(x, valid)
        h, entries = self._prefill(params, tokens, count)
        last = h[jnp.arange(n), jnp.maximum(count - 1, 0)]
        state = self.init_state(n)
        state.update(mean=mean, var=jnp.maximum(var, 1e-6),
                     count=jnp.minimum(count, c.window), pos=count)
        state["hn"] = jnp.where(
            (count > 0)[:, None],
            rms(last, params["norm"], c.rms_norm_eps), 0.0).astype(
                c.compute_dtype)
        return state, entries, count

    def score(self, params: dict, x: jax.Array, valid: jax.Array) -> jax.Array:
        """The newest value's score from a stored window alone (the query
        path): the surprisal of its bin under the positions before it."""
        def rows(x, valid):
            tokens, count, _, _ = self._window_tokens(x, valid)
            h, _ = self._prefill(params, tokens, count)
            return self._window_score(params, h, tokens, count)

        return self._in_blocks(rows, x, valid)

    def _window_score(self, params, h, tokens, count):
        """The newest value's score from a prefill over its window (the
        query path): the surprisal of its bin under the positions
        before it, `h` `[n, S, hidden]` before the final norm."""
        n = h.shape[0]
        at = jnp.maximum(count - 1, 1)
        logits = self._logits(params, h[jnp.arange(n), at - 1])
        surprisal = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tokens[jnp.arange(n), at][:, None], axis=1)[:, 0]
        return jnp.clip(jnp.where(count >= self._gate, surprisal, 0.0),
                        0.0, self.cfg.score_clip)

    def _head(self, params):
        """The head's matrix `[hidden, vocab]`: its own leaf, or the
        embedding's where the two are tied (a product that contracts
        over the embedding's minor dimension: nothing is transposed at
        rest)."""
        return params["head"] if "head" in params else params["embed"].T

    def _logits(self, params, h):
        with jax.named_scope("lm_head"):
            return self._mm(rms(h, params["norm"], self.cfg.rms_norm_eps),
                            self._head(params))

    def _in_blocks(self, fn, *rows):
        """`fn` over row blocks of `seed_rows`, one after another, so a
        whole bucket's windows never stand in memory at once."""
        n, b = rows[0].shape[0], self.seed_rows
        if n <= b:
            return fn(*rows)
        pad = -n % b
        blocks = [jnp.concatenate([r, jnp.zeros((pad,) + r.shape[1:],
                                                r.dtype)]).reshape(
            (-1, b) + r.shape[1:]) for r in rows]
        out = jax.lax.map(lambda xs: fn(*xs), tuple(blocks))
        return jax.tree.map(
            lambda o: o.reshape((-1,) + o.shape[2:])[:n], out)
