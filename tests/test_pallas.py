"""Parity tests for the Pallas fused-window LSTM kernel
(ops/lstm_kernel.py) in interpret mode — the kernel's math must match
the lax.scan reference path it replaces on TPU.

Interpret mode executes the kernel's memory/grid semantics in the
Pallas interpreter on CPU, so these tests pin correctness everywhere;
Mosaic's own compile and on-chip parity are checked by chip_smoke.py
phase B.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sitewhere_tpu.models.common import lstm_init, lstm_scan
from sitewhere_tpu.models.lstm import LstmAnomalyModel, LstmConfig
from sitewhere_tpu.ops.lstm_kernel import (
    B_TILE,
    _pallas_final,
    lstm_window_final,
    pallas_ok,
)


def _final_reference(params, xn, cdt):
    _, (h, _) = lstm_scan(params, xn[:, :, None], cdt)
    return h


def test_kernel_matches_scan_reference_interpret():
    rng = jax.random.PRNGKey(0)
    p = lstm_init(rng, 1, 64)
    xn = jax.random.normal(jax.random.PRNGKey(1), (2 * B_TILE, 63),
                           jnp.float32)
    got = _pallas_final(xn, p["wx"].astype(jnp.bfloat16),
                        p["wh"].astype(jnp.bfloat16),
                        p["b"].reshape(1, -1), interpret=True)
    want = _final_reference(p, xn, jnp.bfloat16)
    assert got.shape == want.shape == (2 * B_TILE, 64)
    # kernel accumulates the matmuls in f32 (one rounding tighter than
    # the scan path's bf16 matmul outputs): agreement to bf16 noise
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2)


def test_kernel_multi_tile_grid_interpret():
    """Rows land in the right output block across grid programs."""
    rng = jax.random.PRNGKey(2)
    p = lstm_init(rng, 1, 64)
    xn = jax.random.normal(jax.random.PRNGKey(3), (4 * B_TILE, 31),
                           jnp.float32)
    got = _pallas_final(xn, p["wx"].astype(jnp.bfloat16),
                        p["wh"].astype(jnp.bfloat16),
                        p["b"].reshape(1, -1), interpret=True)
    # per-tile independence: running one tile alone gives the same rows
    solo = _pallas_final(xn[B_TILE:2 * B_TILE],
                         p["wx"].astype(jnp.bfloat16),
                         p["wh"].astype(jnp.bfloat16),
                         p["b"].reshape(1, -1), interpret=True)
    np.testing.assert_allclose(np.asarray(got[B_TILE:2 * B_TILE]),
                               np.asarray(solo), atol=1e-6)


def test_score_fused_fallback_semantics():
    """On CPU (pallas_ok False) score_fused must be bit-identical to
    score — same function, same path."""
    model = LstmAnomalyModel(LstmConfig(window=32))
    params = model.init(jax.random.PRNGKey(4))
    x = np.random.default_rng(0).standard_normal((300, 32)).astype(np.float32)
    valid = np.ones((300, 32), bool)
    assert not pallas_ok(300, 1)          # CPU backend + non-tile batch
    a = np.asarray(model.score_fused(params, jnp.asarray(x),
                                     jnp.asarray(valid)))
    b = np.asarray(model.score(params, jnp.asarray(x), jnp.asarray(valid)))
    np.testing.assert_array_equal(a, b)


def test_score_fused_kernel_path_parity_interpret():
    """Force the kernel path (interpret) through the same normalize/
    head/gate plumbing score_fused uses on TPU and compare to score."""
    model = LstmAnomalyModel(LstmConfig(window=32))
    params = model.init(jax.random.PRNGKey(5))
    rng = np.random.default_rng(1)
    x = rng.standard_normal((B_TILE, 32)).astype(np.float32) * 3.0 + 20.0
    valid = np.ones((B_TILE, 32), bool)
    valid[: B_TILE // 4, :28] = False      # short-history rows (4 < gate 8)
    xj, vj = jnp.asarray(x), jnp.asarray(valid)

    xn, _, _ = model._normalize(xj, vj.astype(jnp.float32))
    h = lstm_window_final(params["lstm0"], xn[:, :-1],
                          model.cfg.compute_dtype,
                          use_pallas=True, interpret=True)
    head = params["head"]
    pred = (h @ head["w"] + head["b"])[:, 0]
    err = jnp.abs(pred - xn[:, -1])
    enough = vj.sum(-1) >= max(8, model.cfg.window // 8)
    fused = np.asarray(jnp.clip(jnp.where(enough, err, 0.0), 0.0,
                                model.cfg.score_clip))
    ref = np.asarray(model.score(params, xj, vj))
    np.testing.assert_allclose(fused, ref, atol=3e-2)
    # the short-history gate stayed intact
    assert (fused[: B_TILE // 4] == ref[: B_TILE // 4]).all()


def test_pallas_ok_predicate():
    assert not pallas_ok(B_TILE - 8, 1)    # not tile-divisible
    assert not pallas_ok(B_TILE, 2)        # multi-layer
    # non-bf16 compute_dtype must never take the bf16 kernel
    assert not pallas_ok(B_TILE, 1, jnp.float32)
    with pytest.raises(TypeError):
        pallas_ok()                        # args are required


def test_ring_raises_when_selected_fused_scorer_fails_to_compile(
        monkeypatch):
    """A fused scorer that was SELECTED and fails at trace/compile time
    raises out of the ring — no silent rebuild on the scan path. The
    ring's donated state is untouched (AOT compile executes nothing),
    and every later attempt raises again rather than remembering a
    degraded verdict."""
    from sitewhere_tpu.ops import lstm_kernel
    from sitewhere_tpu.scoring.ring import DeviceRing

    # force the fused gate open (CPU would normally decline)
    monkeypatch.setattr(lstm_kernel, "pallas_ok", lambda *a, **k: True)

    model = LstmAnomalyModel(LstmConfig(window=16))
    params = model.init(jax.random.PRNGKey(0))

    def broken_fused(p, x, valid):
        raise RuntimeError("mosaic said no")

    model.score_fused = broken_fused
    ring = DeviceRing(window=16, capacity=64)
    dev = np.arange(8, dtype=np.int32)
    v = np.ones(8, np.float32)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="mosaic said no"):
            ring.update_and_score(model, params, dev, v, 64)
    assert ring.fused_status is None and not ring.faulted
    assert not ring._update_score_fns


def test_ring_probe_keeps_compiled_fn(monkeypatch):
    """When the fused path compiles, the AOT Compiled object is kept —
    dispatch must not pay a second identical compile — and the scores
    match the plain scan path."""
    from sitewhere_tpu.ops import lstm_kernel
    from sitewhere_tpu.scoring.ring import DeviceRing

    model = LstmAnomalyModel(LstmConfig(window=16))
    params = model.init(jax.random.PRNGKey(0))
    dev = np.arange(8, dtype=np.int32)
    v = np.ones(8, np.float32)
    # reference first: on CPU the predicate declines, so this is the scan
    ref = DeviceRing(window=16, capacity=64)
    ref_scores = np.asarray(ref.update_and_score(model, params, dev, v, 64))
    assert ref.fused_status is None

    monkeypatch.setattr(lstm_kernel, "pallas_ok", lambda *a, **k: True)
    # a fused scorer with a compilable body (the monkeypatched gate
    # would otherwise push score_fused onto the real Pallas path, which
    # cannot compile on CPU): the AOT machinery runs end to end
    model.score_fused = model.score
    ring = DeviceRing(window=16, capacity=64)
    scores = np.asarray(ring.update_and_score(model, params, dev, v, 64))
    fn = ring._update_score_fns[(ring.capacity, 64)]
    assert not hasattr(fn, "lower")     # AOT Compiled, not a jit wrapper
    assert ring.fused_status == "compiled"
    np.testing.assert_allclose(scores[:8], ref_scores[:8], atol=1e-5)


def _chain(e, lo, hi, start):
    """The parent's choice of the next weight block (PR 39's kernel): one
    `pl.when` a held expert at every step."""
    from jax.experimental import pallas as pl

    for k in range(lo, hi):
        pl.when(e == k)(lambda k=k: start(k))


@pytest.mark.parametrize("hidden, inter",
                         [(7168, 2048), (3072, 1024), (2048, 1536)],
                         ids=["dsv3_widths", "laguna_widths", "lfm2_widths"])
@pytest.mark.parametrize("held, tile",
                         [(1, 64), (2, 32), (3, 32), (5, 16), (16, 16)])
def test_expert_kernel_matches_the_plain_products_interpret(
        monkeypatch, held, tile, hidden, inter):
    """ops/expert_kernel.py at each served model's published widths
    (hidden 7168, intermediate 2048 in its sixteen blocks of 128; hidden
    3072, intermediate 1024 in eight; hidden 2048, intermediate 1536 in
    twelve) over one to sixteen experts of few rows, against the three
    products `Dsv3StreamModel._mlp` makes of each and one scatter-add:
    bf16 operands, f32 sums, `silu * up` rounded to bf16 once, the
    weight applied in f32, a token's experts summed in f32. The down
    product is summed block by block, so the two differ by the order of
    a float32 sum. A run's rows past its count add nothing, whatever
    their weight: every case sees a full tile, an empty run and a
    partial one. Five experts make a branch tree that is not a power of
    two, sixteen one of four levels; whatever the tree, the output is
    BITWISE the parent's, whose chain tested every expert at every step
    (the same blocks in the same order). (Memory a kernel never wrote
    reads NaN in interpret mode.)"""
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.ops import expert_kernel
    from sitewhere_tpu.ops.expert_kernel import expert_tiles, fits

    tokens = 72
    # a frame of any cell fits, a seeding call's tokens do not
    assert fits(1024, 7168, 2048, 128) and not fits(2048, 7168, 2048, 128)
    assert fits(256, 3072, 1024, 128) and not fits(4224, 3072, 1024, 128)
    assert fits(512, 2048, 1536, 128)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 3 * held + 3))
    experts = [{name: (jax.random.normal(next(keys), shape, jnp.float32)
                       * 0.02).astype(jnp.bfloat16)
                for name, shape in (("gate", (hidden, inter)),
                                    ("up", (hidden, inter)),
                                    ("down", (inter, hidden)))}
               for _ in range(held)]
    rng = np.random.default_rng(held)
    rows = np.concatenate([np.sort(rng.permutation(tokens)[:tile])
                           for _ in range(held)]).astype(np.int32)
    x = jax.random.normal(next(keys), (tokens, hidden)).astype(jnp.bfloat16)
    wts = jax.random.uniform(next(keys), (held * tile,))
    model = build_model("dsv3-stream", num_hidden_layers=1, mtp_modules=0)
    # a full tile, an empty run, a partial one: in turn where fewer than
    # three experts are held
    runs = [np.resize(np.roll([tile, 0, 5], shift), held).astype(np.int32)
            for shift in range(3 if held < 3 else 1)]
    trees = [expert_tiles(experts, x[rows], rows, wts, counts, tokens,
                          interpret=True) for counts in runs]
    monkeypatch.setattr(expert_kernel, "pick", _chain)
    chain = jax.jit(functools.partial(expert_tiles.__wrapped__,
                                      tokens=tokens, interpret=True))
    parents = [chain(experts, x[rows], rows, wts, counts) for counts in runs]
    for counts, got, parent in zip(runs, trees, parents):
        assert (np.asarray(parent).view(np.uint32)
                == np.asarray(got).view(np.uint32)).all()
        real = (np.arange(tile)[None, :] < counts[:, None]).reshape(-1)
        ys = jnp.concatenate([
            model._mlp(expert, x[rows[e * tile:(e + 1) * tile]])
            for e, expert in enumerate(experts)]) * (wts * real)[:, None]
        want = jnp.zeros((tokens, hidden), jnp.float32).at[rows].add(ys)
        assert got.shape == want.shape and got.dtype == jnp.float32
        scale = float(jnp.abs(want).max())
        assert 0.1 < scale < 10 or not counts.any()
        assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
        untouched = np.setdiff1d(np.arange(tokens), rows[real])
        assert (np.asarray(got)[untouched] == 0).all()


@pytest.mark.parametrize("held, tile", [(1, 32), (3, 16), (5, 16)])
def test_ungated_experts_match_the_plain_products_interpret(held, tile):
    """The kernel's two-leaf form at `nemotron-h-stream`'s published
    latent experts (1,024 x 2,688 in 21 blocks of 128, and back), chosen
    by the leaves it is handed, against `_mlp`'s ungated twin and one
    scatter-add: `relu(x up)^2` in f32, rounded to bf16 once before the
    down product, the weight in f32, a token's experts summed in f32; a
    full tile, an empty run and a partial one. It asks VMEM for two
    leaves' blocks where the gated form asks for three."""
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.ops.expert_kernel import expert_tiles, fits, vmem_bytes

    hidden, inter, tokens = 1024, 2688, 40
    assert fits(128, hidden, inter, 128, 2) and fits(2016, hidden, inter,
                                                     128, 2)
    assert vmem_bytes(128, hidden, 128, 2) + 4 * hidden * 128 \
        == vmem_bytes(128, hidden, 128)
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 2 * held + 3))
    experts = [{name: (jax.random.normal(next(keys), shape, jnp.float32)
                       * 0.03).astype(jnp.bfloat16)
                for name, shape in (("up", (hidden, inter)),
                                    ("down", (inter, hidden)))}
               for _ in range(held)]
    rng = np.random.default_rng(held)
    rows = np.concatenate([np.sort(rng.permutation(tokens)[:tile])
                           for _ in range(held)]).astype(np.int32)
    x = jax.random.normal(next(keys), (tokens, hidden)).astype(jnp.bfloat16)
    wts = jax.random.uniform(next(keys), (held * tile,))
    model = build_model("nemotron-h-stream", num_hidden_layers=1,
                        hybrid_override_pattern="E",
                        num_nextn_predict_layers=0)
    counts = np.resize([tile, 0, 5], held).astype(np.int32)
    got = expert_tiles(experts, x[rows], rows, wts, counts, tokens,
                       interpret=True)
    real = (np.arange(tile)[None, :] < counts[:, None]).reshape(-1)
    ys = jnp.concatenate([
        model._mlp(expert, x[rows[e * tile:(e + 1) * tile]])
        for e, expert in enumerate(experts)]) * (wts * real)[:, None]
    want = jnp.zeros((tokens, hidden), jnp.float32).at[rows].add(ys)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert 0.1 < scale < 10
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
    untouched = np.setdiff1d(np.arange(tokens), rows[real])
    assert (np.asarray(got)[untouched] == 0).all()


# -- ops/state_kernel.py: a matrix state updated where it rests ---------------

LINEAR, FULL = "linear_attention", "full_attention"
# (heads, keys a head, values a head): the published head sizes, rested
# `[rows, 15, 96, 384]`, and a small shape, four heads to a row of lanes
STATE_SHAPES = {"published_heads": (30, 96, 192), "small": (8, 16, 32)}


def _linear_model(heads, dk, dv, **over):
    from sitewhere_tpu.models import build_model

    return build_model("olmo-hybrid-stream", **{**dict(
        compute_dtype=jnp.float32, hidden_size=128, intermediate_size=256,
        num_hidden_layers=1, layer_types=[LINEAR], num_attention_heads=2,
        num_key_value_heads=2, vocab_size=64, linear_num_key_heads=heads,
        linear_num_value_heads=heads, linear_key_head_dim=dk,
        linear_value_head_dim=dv, window=8, context_positions=16), **over})


def _interpreted(monkeypatch):
    """`update_rows` in interpret mode wherever the model calls it."""
    import functools

    from sitewhere_tpu.ops import state_kernel

    monkeypatch.setattr(state_kernel, "update_rows", functools.partial(
        state_kernel.update_rows, interpret=True))


def _a_layers_inputs(model, rows, frame, seed):
    """A state table of `rows` rows and what a frame of `frame` events
    brings a linear layer: (`p`, table, taps, z, alpha, beta)."""
    c = model.cfg
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 6))
    p = {"conv": jax.random.normal(next(keys), (c.linear_conv_kernel_dim,
                                                c.conv_channels)) * 0.5}
    table = jax.random.normal(next(keys), (rows,) + model._state_shape) * 0.3
    taps = jax.random.normal(next(keys), (frame, 3 * c.conv_channels))
    z = jax.random.normal(next(keys), (frame, c.conv_channels))
    alpha = jax.random.uniform(next(keys), (frame, c.linear_num_value_heads),
                               minval=0.2, maxval=1.0)
    beta = jax.random.uniform(next(keys), (frame, c.linear_num_value_heads),
                              minval=0.0, maxval=2.0)
    return p, table, taps, z, alpha, beta


@pytest.mark.parametrize("shape", STATE_SHAPES)
def test_state_kernel_matches_the_cell_and_writes_no_other_row_interpret(
        shape, monkeypatch):
    """ops/state_kernel.py against `_gdn_cell` on random states and
    operands: the next state to 1e-5 of its scale, `o` to 1e-4, the
    largest magnitude a row held exactly (a maximum has no order); rows
    the frame does not name are bit-equal after the call, the scratch
    row among them; padding (`dev` past the scratch row) writes nothing,
    and its `o` and magnitude read 0; a SECOND dispatch over rows that
    overlap the first's equals two plain steps."""
    from sitewhere_tpu.ops import state_kernel
    from sitewhere_tpu.scoring.stream import pad_rows

    model = _linear_model(*STATE_SHAPES[shape])
    rows, frame, live = 7, 6, 4
    p, table, taps, z, alpha, beta = _a_layers_inputs(model, rows, frame, 3)
    assert state_kernel.fits(table.shape, table.dtype)
    if shape == "published_heads":
        assert table.shape[1:] == (15, 96, 384)
    _interpreted(monkeypatch)
    scratch = rows - 1

    def plain(table, dev):
        o, s, _, held = model._gdn_cell(p, table[jnp.minimum(dev, scratch)],
                                        taps, z, alpha, beta)
        return table.at[dev].set(s, mode="drop"), o, held

    def kernel(table, dev):
        table, o, _, held, n = model._gdn_rows(p, table, dev, taps, z,
                                               alpha, beta)
        return table, o, held, n

    first = np.concatenate([[0, 2, 3, 5], pad_rows(scratch, frame - live)])
    second = np.concatenate([[1, 2, 5], pad_rows(scratch, frame - 3)])
    want, got = table, table
    for dev, n_live in ((first, live), (second, 3)):
        dev = jnp.asarray(dev, jnp.int32)
        before = np.asarray(got)
        want, o_want, held_want = jax.jit(plain)(want, dev)
        got, o_got, held_got, n = jax.jit(kernel)(got, dev)
        assert int(n) == n_live
        scale = float(jnp.abs(want).max())
        assert 0.5 < scale < 10
        assert float(jnp.abs(got - want).max()) < 1e-5 * scale
        o_scale = float(jnp.abs(o_want[:n_live]).max())
        assert float(jnp.abs(o_got - o_want)[:n_live].max()) < 1e-4 * o_scale
        assert (np.asarray(held_got)[:n_live]
                == np.asarray(held_want)[:n_live]).all()
        assert not np.asarray(o_got)[n_live:].any()
        assert not np.asarray(held_got)[n_live:].any()
        unnamed = np.setdiff1d(np.arange(rows), np.asarray(dev)[:n_live])
        assert (np.asarray(got)[unnamed] == before[unnamed]).all()
        named = np.asarray(dev)[:n_live]
        assert (np.asarray(got)[named] != before[named]).any(axis=(1, 2, 3)
                                                            ).all()


# (heads, head width, state, groups of B and C): the published Mamba-2
# layer, a row of 4 MiB taken in two blocks of 32 rows of lanes, and a
# small one, a row of one block
MAMBA_SHAPES = {"published_heads": (128, 64, 128, 8), "small": (4, 64, 64, 2)}


def _mamba_model(heads, dim, state, groups):
    from sitewhere_tpu.models import build_model

    return build_model(
        "nemotron-h-stream", compute_dtype=jnp.float32, hidden_size=128,
        expand=heads * dim // 128, mamba_num_heads=heads,
        mamba_head_dim=dim, ssm_state_size=state, n_groups=groups,
        num_hidden_layers=1, hybrid_override_pattern="M",
        num_nextn_predict_layers=0, vocab_size=64, window=8,
        context_positions=16)


@pytest.mark.parametrize("shape", MAMBA_SHAPES)
def test_state_kernel_decay_rule_matches_the_mamba2_cell_interpret(
        shape, monkeypatch):
    """ops/state_kernel.py's decay-and-write rule (three vectors a row)
    against `NemotronHStreamModel._ssm_cell` on random states and
    operands: the next state to 1e-5 of its scale, `y` to 1e-4, the
    largest magnitude a row held exactly; at the published widths the
    row is taken in two blocks, and what each block found is one row's
    again; rows the frame does not name are bit-equal after the call,
    the scratch row among them; padding writes nothing and reads 0; a
    second dispatch over rows that overlap the first's equals two plain
    steps."""
    from sitewhere_tpu.ops import state_kernel
    from sitewhere_tpu.scoring.stream import pad_rows

    model = _mamba_model(*MAMBA_SHAPES[shape])
    c = model.cfg
    rows, frame, live = 5, 4, 3
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 6))
    p = {"conv": jax.random.normal(next(keys), (4, c.conv_channels)) * 0.5,
         "conv_bias": jnp.zeros(c.conv_channels),
         "D": jnp.linspace(0.5, 1.5, c.mamba_num_heads)}
    table = jax.random.normal(next(keys), (rows,) + model._state_shape) * 0.3
    taps = jax.random.normal(next(keys), (frame, 3 * c.conv_channels))
    xbc = jax.random.normal(next(keys), (frame, c.conv_channels))
    dt = jax.random.uniform(next(keys), (frame, c.mamba_num_heads),
                            minval=0.01, maxval=0.5)
    a = jnp.exp(-dt * 4.0)
    assert state_kernel.fits(table.shape, table.dtype)
    assert state_kernel.blocks(table.shape) == (2 if shape == "published_heads"
                                                else 1)
    _interpreted(monkeypatch)
    scratch = rows - 1

    def plain(table, dev):
        y, s, _, held = model._ssm_cell(p, table[jnp.minimum(dev, scratch)],
                                        taps, xbc, dt, a)
        return table.at[dev].set(s, mode="drop"), y, held

    def kernel(table, dev):
        table, y, _, held, n = model._ssm_rows(p, table, dev, taps, xbc, dt,
                                               a)
        return table, y, held, n

    first = np.concatenate([[0, 1, 3], pad_rows(scratch, frame - live)])
    second = np.concatenate([[1, 2], pad_rows(scratch, frame - 2)])
    want, got = table, table
    for dev, n_live in ((first, live), (second, 2)):
        dev = jnp.asarray(dev, jnp.int32)
        before = np.asarray(got)
        want, y_want, held_want = jax.jit(plain)(want, dev)
        got, y_got, held_got, n = jax.jit(kernel)(got, dev)
        assert int(n) == n_live
        scale = float(jnp.abs(want).max())
        assert 0.5 < scale < 10
        assert float(jnp.abs(got - want).max()) < 1e-5 * scale
        y_scale = float(jnp.abs(y_want[:n_live]).max())
        assert float(jnp.abs(y_got - y_want)[:n_live].max()) < 1e-4 * y_scale
        assert (np.asarray(held_got)[:n_live]
                == np.asarray(held_want)[:n_live]).all()
        assert not np.asarray(held_got)[n_live:].any()
        unnamed = np.setdiff1d(np.arange(rows), np.asarray(dev)[:n_live])
        assert (np.asarray(got)[unnamed] == before[unnamed]).all()


def test_state_kernel_takes_float32_rows_of_whole_tiles_that_vmem_holds():
    """`fits` reads the leaf's shape and dtype: the published rows and the
    tests' small ones; not a bfloat16 leaf, a row of keys that is no
    whole sublane tile, lanes that are no whole lane tile, nor a row of
    which no block of whole groups, four blocks at a time, stays under
    the VMEM the call asks for; a row that four of pass it whole is
    taken in the fewest blocks of its groups that do not (`blocks`); and
    `update_rows` refuses what `fits` does not take."""
    from sitewhere_tpu.ops import state_kernel

    fits = state_kernel.fits
    assert fits((769, 15, 96, 384), jnp.float32)
    assert state_kernel.blocks((769, 15, 96, 384)) == 1
    assert state_kernel.vmem_bytes((769, 15, 96, 384)) < 10 << 20
    # a Mamba-2 row of 128 heads of 64 over a state of 128: 4 MiB, two
    # blocks of 2 MiB
    assert fits((385, 64, 128, 128), jnp.float32)
    assert state_kernel.blocks((385, 64, 128, 128)) == 2
    assert state_kernel.vmem_bytes((385, 64, 128, 128)) > 12 << 20
    assert state_kernel.vmem_bytes((385, 64, 128, 128), 2) < 10 << 20
    assert fits((7, 2, 16, 128), jnp.float32)
    assert not fits((769, 15, 96, 384), jnp.bfloat16)
    assert not fits((769, 15, 92, 384), jnp.float32)
    assert not fits((769, 15, 96, 192), jnp.float32)
    assert fits((769, 30, 96, 384), jnp.float32)
    assert state_kernel.blocks((769, 30, 96, 384)) == 2
    assert not fits((769, 1, 1024, 1024), jnp.float32)
    assert state_kernel.blocks((769, 1, 1024, 1024)) == 0
    assert not fits((769, 96, 5760), jnp.float32)
    with pytest.raises(ValueError, match="takes no table"):
        state_kernel.update_rows(
            jnp.zeros((3, 1, 12, 128)), jnp.zeros(2, jnp.int32),
            jnp.zeros((2, 2, 12, 2)), jnp.zeros((2, 4, 1, 128)),
            interpret=True)


@pytest.mark.parametrize("case, heads, in_place", [
    ("rows_the_kernel_takes", (4, 16, 64), True),
    ("keys_that_are_no_whole_tile", (16, 12, 32), False)])
def test_the_step_lowered_for_a_tpu_is_the_plain_step(case, heads, in_place,
                                                      monkeypatch):
    """The whole ring step with the TPU's branch taken (the kernel in
    interpret mode) against the step as the CPU lowers it: scores and
    every state leaf to float32 round-off, the step's other numbers
    equal, and `state.in_place` counts the live rows of every linear
    layer where the branch ran, 0 on the plain path. A leaf `fits` does
    not take never reaches the choice: the plain path runs on any
    platform."""
    from sitewhere_tpu.scoring.stream import pad_rows, streaming_step

    model = _linear_model(*heads, num_hidden_layers=4,
                          layer_types=[LINEAR] * 3 + [FULL])
    params = model.init(jax.random.PRNGKey(0))
    cap, frame, live = 9, 8, 5
    state = model.init_state(cap + 1)
    for name in state:
        if name[0] in "sc":
            state[name] = jax.random.normal(
                jax.random.PRNGKey(len(name)), state[name].shape) * 0.2
    dev = np.concatenate([[0, 1, 4, 6, 8], pad_rows(cap, frame - live)]
                         ).astype(np.int32)
    v = np.linspace(-1, 1, frame).astype(np.float32)
    want_state, want = jax.jit(streaming_step(model))(params, state, dev, v)

    def on_a_tpu(*args, default, tpu):
        return tpu(*args)

    _interpreted(monkeypatch)
    monkeypatch.setattr(jax.lax, "platform_dependent", on_a_tpu)
    got_state, got = jax.jit(streaming_step(model))(params, state, dev, v)
    stats = len(model.step_stats)
    # (the full layer's context is float32 here, heads of 64: no leaf
    # for ops/context_kernel.py, whose count stays 0 on both sides)
    assert model.step_stats[-3:] == ("state.in_place", "ctx.at_rest",
                                     "ctx.read_positions")
    assert float(want[-3]) == 0 and not want[-2:].any() and not got[-2:].any()
    assert float(got[-3]) == (3 * live if in_place else 0)
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5)
    np.testing.assert_allclose(got[-stats:-3], want[-stats:-3], rtol=1e-6)
    for name, leaf in want_state.items():
        np.testing.assert_allclose(got_state[name], leaf, atol=1e-5,
                                   err_msg=name)


# -- ops/context_kernel.py: a stored context read where it rests ---------------

# (head width, positions, key-value heads, query heads, wraps, each live
# row's position, padding rows): heads of 128, as three served models'
# are, and of 64, two to a lane tile, as `lfm2-stream`'s are
CONTEXT_CASES = {
    # a full layer's leaf, grouped heads: the first position, the middle,
    # the last the leaf holds
    "bounded_grouped_heads": (128, 32, 2, 6, False, [0, 13, 31], 1),
    # a sliding layer's: before it has wrapped, the position that fills
    # it, the first that overwrites, and far round the circle
    "wrapping_before_and_after": (128, 32, 2, 4, True, [5, 31, 32, 77], 2),
    # `olmo-hybrid-stream`'s: a query head a key-value head, no group
    "a_head_a_key_value_head": (128, 48, 3, 3, False, [0, 20, 47], 0),
    "a_frame_of_one_live_row": (128, 32, 2, 4, False, [9], 3),
    # heads of half a lane tile: grouped, behind padding rows
    "half_tile_heads_grouped": (64, 32, 2, 6, False, [0, 13, 31], 2),
    # `lfm2-stream`'s own grouping, 32 query heads on 8
    "half_tile_heads_32_on_8": (64, 32, 8, 32, False, [1, 16, 30], 1),
    # a wrapping context of such heads, before and after it has wrapped
    "half_tile_heads_wrapping": (64, 32, 2, 4, True, [5, 31, 32, 77], 1),
    # `laguna-stream`'s full layer at its own widths, 48 query heads on 8
    "laguna_48_heads_on_8": (128, 32, 8, 48, False, [0, 17, 31], 1),
}


def _context_takes(d, *leaf):
    """Whether `context_rows` takes the leaf by the one predicate of its
    head width `d`, and the other refuses it."""
    from sitewhere_tpu.ops import context_kernel

    whole = context_kernel.fits(*leaf)
    paired = context_kernel.fits_paired(*leaf)
    return (paired and not whole) if d == 64 else (whole and not paired)


def _blocks():
    """`SeqBlocks` with bfloat16 products and nothing else: what
    `_decode_at_rest` and `_decode_rows` read of a model."""
    from sitewhere_tpu.models.seqblocks import SeqBlocks

    class Blocks(SeqBlocks):
        class cfg:
            compute_dtype = jnp.bfloat16
        _scale = 128 ** -0.5

    return Blocks()


def _context_interpreted(monkeypatch):
    """`context_rows` in interpret mode wherever a model calls it, and
    the TPU's branch taken wherever the code asks the platform."""
    import functools

    from sitewhere_tpu.ops import context_kernel

    monkeypatch.setattr(context_kernel, "context_rows", functools.partial(
        context_kernel.context_rows, interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, default, tpu: tpu(*args))


@pytest.mark.parametrize("case", CONTEXT_CASES)
def test_context_kernel_matches_the_decode_form_and_writes_nothing_interpret(
        case, monkeypatch):
    """ops/context_kernel.py through `SeqBlocks._decode_at_rest` against
    the plain path (the ring's gather, `_decode_rows`, the append): the
    heads' outputs to float32 round-off (bfloat16 operands and float32
    sums on both sides: only the order of a sum differs), a padding
    row's 0; both tables bit-equal to the plain path's, which differ
    from what they were by the appended entries alone, the scratch row
    untouched; and the kernel's branch counts the live rows it read
    where the plain one counts 0."""
    from sitewhere_tpu.ops import context_kernel
    from sitewhere_tpu.scoring.stream import ContextAtRest, pad_rows

    d, positions, kv, heads, wraps, at, padding = CONTEXT_CASES[case]
    rows, live = 11, len(at)
    scratch = rows - 1
    keys = iter(jax.random.split(jax.random.PRNGKey(positions + heads), 5))
    tables = [jax.random.normal(next(keys), (rows, positions, kv * d)
                                ).astype(jnp.bfloat16) for _ in range(2)]
    assert _context_takes(d, tables[0].shape, tables[0].dtype, heads, kv)
    frame = live + padding
    q = jax.random.normal(next(keys), (frame, heads, d)) * 2.0
    k, v = (jax.random.normal(next(keys), (frame, kv * d)).astype(
        jnp.bfloat16) for _ in range(2))
    dev = jnp.asarray(np.concatenate([
        np.sort(np.random.default_rng(live).permutation(scratch)[:live]),
        pad_rows(scratch, padding)]), jnp.int32)
    # padding reads the scratch row's position, 0
    pos = jnp.asarray(at + [0] * padding, jnp.int32)
    slot = pos % positions if wraps else pos
    blocks = _blocks()

    def attend(ktab, vtab):
        kctx, vctx = (ContextAtRest(t, dev, slot) for t in (ktab, vtab))
        out = blocks._decode_at_rest(q, k, v, kctx, vctx, pos, kv, wraps)
        return out, kctx.table, vctx.table, kctx.read_rows

    # (a jit of its own each: the second trace takes the other branch)
    want, *want_tables, plain_rows = jax.jit(
        lambda ktab, vtab: attend(ktab, vtab))(*tables)
    _context_interpreted(monkeypatch)
    got, *got_tables, read_rows = jax.jit(
        lambda ktab, vtab: attend(ktab, vtab))(*tables)
    assert int(plain_rows) == 0 and int(read_rows) == live
    scale = float(jnp.abs(want[:live]).max())
    assert 0.3 < scale < 10
    assert float(jnp.abs(got - want)[:live].max()) < 2e-6 * scale
    assert not np.asarray(got)[live:].any()
    for was, plain, rested, entry in zip(tables, want_tables, got_tables,
                                         (k, v)):
        assert (np.asarray(rested) == np.asarray(plain)).all()
        appended = np.asarray(was.at[dev[:live], slot[:live]].set(
            entry[:live]))
        assert (np.asarray(rested) == appended).all()
        assert (np.asarray(rested)[scratch] == np.asarray(was)[scratch]).all()


# (head width, positions, key-value heads, query heads, contexts a row,
# the one read, each live row's position, padding rows): `ouro-stream`'s
# tables, a (pass, layer) a block of lanes, the position's own entry
# beside them
BLOCK_CASES = {
    "the_third_of_four_a_head_a_key_value_head": (128, 32, 2, 2, 4, 2,
                                                  [0, 13, 31], 1),
    "the_last_of_three_grouped_heads": (128, 48, 2, 4, 3, 2, [7, 47], 2),
    # positions that are no whole lane tile of `probs` (448 is 3.5)
    "positions_of_half_a_lane_tile": (128, 64 + 16, 2, 2, 2, 1, [0, 70, 79],
                                      1),
    # heads of half a lane tile, grouped: the second block of three
    "the_second_of_three_half_tile_heads": (64, 32, 4, 8, 3, 1,
                                            [0, 20, 31], 1),
}


@pytest.mark.parametrize("case", BLOCK_CASES)
def test_context_kernel_reads_a_block_beside_the_own_entry_interpret(case):
    """`context_rows` over one block of lanes of a row of contexts, the
    position's own entry handed beside the tables, whose slot at `pos`
    holds something stale: against `_decode_rows` over the gathered
    block with the entry laid in, to float32 round-off; a padding row's
    0; another block answers otherwise; no table is written."""
    from sitewhere_tpu.ops import context_kernel
    from sitewhere_tpu.scoring.stream import ContextAtRest, pad_rows

    d, positions, kv, heads, blocks, block, at, padding = BLOCK_CASES[case]
    rows, live = 11, len(at)
    width = kv * d
    scratch = rows - 1
    keys = iter(jax.random.split(jax.random.PRNGKey(positions + blocks), 5))
    tables = [jax.random.normal(next(keys), (rows, positions, blocks * width)
                                ).astype(jnp.bfloat16) for _ in range(2)]
    assert _context_takes(d, tables[0].shape, tables[0].dtype, heads, kv,
                          width)
    frame = live + padding
    q = jax.random.normal(next(keys), (frame, heads, d)) * 2.0
    k, v = (jax.random.normal(next(keys), (frame, width)).astype(
        jnp.bfloat16) for _ in range(2))
    dev = jnp.asarray(np.concatenate([
        np.sort(np.random.default_rng(live).permutation(scratch)[:live]),
        pad_rows(scratch, padding)]), jnp.int32)
    pos = jnp.asarray(at + [0] * padding, jnp.int32)
    blocks_ = _blocks()

    def plain(block):
        ktab, vtab = (ContextAtRest(t, dev, pos) for t in tables)
        return blocks_._decode_rows(q, k, v, ktab.rows(block, width),
                                    vtab.rows(block, width), pos, kv)

    want = jax.jit(plain)(block)
    got = context_kernel.context_rows(*tables, dev, pos, q, block, (k, v),
                                      kv=kv, scale=128 ** -0.5,
                                      interpret=True)
    scale = float(jnp.abs(want[:live]).max())
    assert 0.3 < scale < 10
    assert float(jnp.abs(got - want)[:live].max()) < 2e-6 * scale
    assert not np.asarray(got)[live:].any()
    other = jax.jit(plain)(block - 1)
    assert float(jnp.abs(other - want)[:live].max()) > 0.1 * scale


# (head width, key-value heads, query heads, contexts a row (0: a row is
# one context and holds the own entry), wraps, each live row's position,
# padding rows): tables of 192 positions, which a row copies in blocks of
# 64 up to what it attends to
PREFIX_CASES = {
    # the first position, either side of a block's edge, the last
    "block_edges": (128, 2, 4, 0, False, [0, 63, 64, 65, 191], 1),
    "block_edges_heads_of_half_a_lane_tile": (64, 2, 4, 0, False,
                                              [0, 63, 64, 65, 191], 2),
    # the own entry beside the table: position `pos` is not copied, so 64
    # positions are one block
    "block_edges_beside_the_own_entry": (128, 2, 4, 3, False,
                                         [0, 63, 64, 65, 191], 1),
    # rows that have wrapped copy the whole row, beside rows that have not
    "wrapped_rows": (128, 2, 4, 0, True, [5, 191, 192, 300], 1),
    # each row's copy another length than the row's before it, which was
    # in flight while that row computed
    "lengths_that_alternate": (128, 2, 2, 0, False,
                               [190, 3, 127, 64, 0, 150], 3),
    "lengths_that_alternate_half_tile_heads_beside_the_own_entry": (
        64, 2, 4, 2, False, [190, 3, 127, 64, 0, 150], 2),
}


@pytest.mark.parametrize("case", PREFIX_CASES)
def test_context_kernel_copies_only_the_positions_a_row_holds_interpret(
        case):
    """`context_rows` copies a row's position blocks up to what the row
    attends to and no further (`position_block`: 64 of 192 here): with
    every position past them, and the whole scratch row, made NaN, the
    heads' outputs are bit-equal to those over the clean tables and
    match `_decode_rows` over the clean rows, a padding row's 0; `reads`
    counts the live rows and, over them, `ceil(len / 64) * 64`
    positions, `len` the row's `min(pos + 1, 192)` (`min(pos, 192)`
    beside the own entry), a block at the least."""
    from sitewhere_tpu.ops import context_kernel
    from sitewhere_tpu.scoring.stream import ContextAtRest, pad_rows

    d, kv, heads, blocks, wraps, at, padding = PREFIX_CASES[case]
    positions, rows, live = 192, 11, len(at)
    assert context_kernel.position_block(positions) == 64
    own, width, scratch = blocks > 0, kv * d, rows - 1
    keys = iter(jax.random.split(jax.random.PRNGKey(live + d), 5))
    tables = [jax.random.normal(next(keys), (
        rows, positions, max(blocks, 1) * width)).astype(jnp.bfloat16)
        for _ in range(2)]
    assert _context_takes(d, tables[0].shape, tables[0].dtype, heads, kv,
                          width)
    frame = live + padding
    q = jax.random.normal(next(keys), (frame, heads, d)) * 2.0
    k, v = (jax.random.normal(next(keys), (frame, width)).astype(
        jnp.bfloat16) for _ in range(2))
    dev = jnp.asarray(np.concatenate([
        np.sort(np.random.default_rng(live).permutation(scratch)[:live]),
        pad_rows(scratch, padding)]), jnp.int32)
    pos = jnp.asarray(at + [0] * padding, jnp.int32)
    slot = pos % positions if wraps else pos
    block = blocks - 1 if own else None
    if not own:
        # the ring's append: the own entry is in the table
        tables = [t.at[dev[:live], slot[:live]].set(e[:live])
                  for t, e in zip(tables, (k, v))]
    ktab, vtab = (ContextAtRest(t, dev, slot) for t in tables)
    want = jax.jit(lambda: _blocks()._decode_rows(
        q, k, v, ktab.rows(block, width), vtab.rows(block, width), pos, kv,
        wraps))()
    held = np.minimum(np.asarray(at) + (0 if own else 1), positions)
    copied = np.maximum(-(-held // 64), 1) * 64
    poisoned = []
    for t in tables:
        t = np.array(t.astype(jnp.float32))
        for row, n in zip(np.asarray(dev[:live]), copied):
            t[row, n:] = np.nan
        t[scratch] = np.nan
        poisoned.append(jnp.asarray(t, jnp.bfloat16))
    got, clean = (context_kernel.context_rows(
        *read, dev, pos, q, block, (k, v) if own else None, kv=kv,
        scale=128 ** -0.5, interpret=True) for read in (poisoned, tables))
    assert (np.asarray(got) == np.asarray(clean)).all()
    scale = float(jnp.abs(want[:live]).max())
    assert 0.3 < scale < 10
    # float32 round-off, and a weight that a float32 sum in another order
    # rounds to the neighbouring bfloat16: 2^-8 of a weight of about
    # 1/192 times a value of about 3, 1.5e-5 of the scale (8.9e-6 in the
    # wrapped case's row at 191, as the kernel read whole rows before)
    assert float(jnp.abs(got - want)[:live].max()) < 3e-5 * scale
    assert not np.asarray(got)[live:].any()
    rows_read, positions_read = context_kernel.reads(
        tables[0].shape, dev, pos, own)
    assert (int(rows_read), int(positions_read)) == (live, copied.sum())


def test_context_kernel_takes_bfloat16_rows_of_whole_tiles_that_vmem_holds():
    """`fits` (heads of whole lane tiles) and `fits_paired` (heads of
    half of one) read the leaf's shape and dtype and the heads: the four
    served leaves, each by one of the two; not a float32 leaf, positions
    that are no whole sublane tile, a context that is no whole lane
    tiles, a key-value head that is neither whole lane tiles nor half of
    one, query heads that are no whole groups, nor a row four of which
    pass the VMEM a call may ask for; and `context_rows` refuses what
    neither takes."""
    from sitewhere_tpu.ops import context_kernel

    fits, paired = context_kernel.fits, context_kernel.fits_paired

    def takes(*leaf):
        return fits(*leaf) or paired(*leaf)

    for shape, heads, kv in (((769, 768, 1024), 48, 8),
                             ((769, 512, 1024), 72, 8),
                             ((769, 384, 3840), 30, 30),
                             ((2561, 512, 512), 32, 8)):
        assert takes(shape, jnp.bfloat16, heads, kv)
        assert context_kernel.vmem_bytes(shape, heads, kv) < 16 << 20
        assert not takes(shape, jnp.float32, heads, kv)
    # `lfm2-stream`'s: heads of 64, about 4.5 MB of VMEM, paired alone
    assert 4.5e6 < context_kernel.vmem_bytes((2561, 512, 512), 32, 8) < 4.6e6
    assert paired((2561, 512, 512), jnp.bfloat16, 32, 8)
    assert not fits((2561, 512, 512), jnp.bfloat16, 32, 8)
    assert fits((7, 32, 256), jnp.bfloat16, 4, 2)
    assert not paired((7, 32, 256), jnp.bfloat16, 4, 2)
    assert paired((7, 32, 128), jnp.bfloat16, 4, 2)       # heads of 64
    assert not fits((7, 32, 128), jnp.bfloat16, 4, 2)
    assert not takes((7, 40, 256), jnp.bfloat16, 4, 2)
    assert not takes((7, 32, 192), jnp.bfloat16, 6, 3)    # 1.5 lane tiles
    assert not takes((7, 32, 192), jnp.bfloat16, 4, 2)    # heads of 96
    assert not takes((7, 32, 384), jnp.bfloat16, 4, 2)    # heads of 192
    assert not takes((7, 32, 64), jnp.bfloat16, 4, 2)     # heads of 32
    assert not takes((7, 32, 256), jnp.bfloat16, 3, 2)
    assert not takes((769, 4096, 1024), jnp.bfloat16, 48, 8)
    assert not takes((769, 768, 8, 128), jnp.bfloat16, 48, 8)
    with pytest.raises(ValueError, match="takes no tables"):
        context_kernel.context_rows(
            jnp.zeros((3, 32, 256)), jnp.zeros((3, 32, 256)),
            jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
            jnp.zeros((2, 4, 128)), kv=2, scale=1.0, interpret=True)


# (each live row's position, padding rows): `dsv3-stream`'s latent
# context at the published widths, ONE table of 192 positions a row, 512
# lanes of values then 64 of the rope part then zeros, which every one of
# 128 query heads reads whole
LATENT_CASES = {
    # a frame of eight: one grid step of `LATENT_ROWS`, padding among them
    "the_first_a_middle_and_the_last_position": ([0, 95, 191], 5),
    "a_frame_of_one_live_row": ([140], 3),
}


def _published_mla():
    from sitewhere_tpu.models import build_model

    return build_model("dsv3-stream", num_hidden_layers=1, mtp_modules=0)


@pytest.mark.parametrize("case", LATENT_CASES)
def test_the_latent_context_is_read_once_where_it_rests_interpret(
        case, monkeypatch):
    """ops/context_kernel.py's one-table form through
    `Dsv3StreamModel._attend_at_rest` against the plain path (the ring's
    gather, `_attend_decode`, the append) at the published widths: the
    heads' outputs to the order of float32 sums (bfloat16 operands on
    both sides, the weighted latents rounded to bfloat16 once on both
    sides, where the next product casts them), a padding row's 0, at one
    grid step of eight rows and at a frame of four; the table bit-equal
    to the plain path's, which differs from what it was by the appended
    entries alone, the scratch row untouched; and the kernel's branch
    counts the live rows it read where the plain one counts 0."""
    from sitewhere_tpu.ops import context_kernel
    from sitewhere_tpu.scoring.stream import ContextAtRest, pad_rows

    model = _published_mla()
    c = model.cfg
    assert (c.num_attention_heads, c.kv_lora_rank, c.entry_width) == (
        128, 512, 640)
    at, padding = LATENT_CASES[case]
    rows, live = 7, len(at)
    scratch = rows - 1
    frame = live + padding
    keys = iter(jax.random.split(jax.random.PRNGKey(live), 6))
    stored = jnp.arange(c.entry_width) < c.latent_width
    table = jnp.where(stored, jax.random.normal(
        next(keys), (rows, c.context_positions, c.entry_width)),
        0.0).astype(jnp.bfloat16)
    q_nope = jax.random.normal(next(keys), (frame, 128, 128))
    q_rope = jax.random.normal(next(keys), (frame, 128, 64))
    entry = jnp.where(stored, jax.random.normal(
        next(keys), (frame, c.entry_width)), 0.0).astype(jnp.bfloat16)
    p = {"kv_b": (0.05 * jax.random.normal(
        next(keys), (512, 128 * 256))).astype(jnp.bfloat16)}
    dev = jnp.asarray(np.concatenate([
        np.sort(np.random.default_rng(live).permutation(scratch)[:live]),
        pad_rows(scratch, padding)]), jnp.int32)
    pos = jnp.asarray(at + [0] * padding, jnp.int32)
    assert context_kernel.fits_latent(table.shape, table.dtype, 128, 512)

    def attend(table):
        ctx = ContextAtRest(table, dev, pos)
        out = model._attend_at_rest(p, q_nope, q_rope, entry, ctx, pos)
        return out, ctx.table, ctx.read_rows

    # (a jit of its own each: the second trace takes the other branch)
    want, want_table, plain_rows = jax.jit(lambda t: attend(t))(table)
    _context_interpreted(monkeypatch)
    got, got_table, read_rows = jax.jit(lambda t: attend(t))(table)
    assert int(plain_rows) == 0 and int(read_rows) == live
    scale = float(jnp.abs(want[:live]).max())
    assert 0.3 < scale < 10
    # a float32 sum in another order may round a weighted latent to the
    # neighbouring bfloat16 (2^-8 of it, times a value weight of about
    # 0.05): 6.4e-5 of the scale read here, at eight rows a grid step
    assert float(jnp.abs(got - want)[:live].max()) < 2e-3 * scale
    assert not np.asarray(got)[live:].any()
    assert (np.asarray(got_table) == np.asarray(want_table)).all()
    appended = np.asarray(table.at[dev[:live], pos[:live]].set(entry[:live]))
    assert (np.asarray(got_table) == appended).all()
    assert (np.asarray(got_table)[scratch] == np.asarray(table)[scratch]).all()


def test_the_one_table_form_takes_bfloat16_tables_of_whole_tiles():
    """`fits_latent` reads the table's shape and dtype, the heads and the
    value width: `deepseek-v3-ep16`'s five tables (4,097 rows) in about
    11.3 MB of VMEM, eight rows a grid step; not a float32 table,
    positions that are no whole sublane tile, a row or a value width that
    is no whole lane tiles, values wider than the row, nor a row whose
    blocks VMEM cannot hold; and `context_rows` refuses a table it does
    not take, or one handed with a block, an own entry or key-value
    heads."""
    from sitewhere_tpu.ops import context_kernel

    fits = context_kernel.fits_latent
    assert fits((4097, 192, 640), jnp.bfloat16, 128, 512)
    assert context_kernel.LATENT_ROWS == 8
    assert 11.0e6 < context_kernel.latent_vmem_bytes((4097, 192, 640), 128,
                                                     512) < 11.6e6
    assert not fits((4097, 192, 640), jnp.float32, 128, 512)
    assert not fits((4097, 200, 640), jnp.bfloat16, 128, 512)
    assert not fits((4097, 192, 576), jnp.bfloat16, 128, 512)
    assert not fits((4097, 192, 640), jnp.bfloat16, 128, 576)
    assert not fits((4097, 192, 640), jnp.bfloat16, 128, 768)
    assert not fits((4097, 8192, 640), jnp.bfloat16, 128, 512)
    assert not fits((4097, 192, 5, 128), jnp.bfloat16, 128, 512)
    table = jnp.zeros((3, 32, 256), jnp.bfloat16)
    dev, pos = jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32)
    with pytest.raises(ValueError, match="takes no table"):
        context_kernel.context_rows(table.astype(jnp.float32), None, dev,
                                    pos, jnp.zeros((2, 4, 256)), scale=1.0,
                                    value_width=128, interpret=True)
    with pytest.raises(ValueError, match="takes no table"):
        context_kernel.context_rows(table, None, dev, pos,
                                    jnp.zeros((2, 4, 128)), scale=1.0,
                                    value_width=128, interpret=True)
    with pytest.raises(ValueError, match="reads one table whole"):
        context_kernel.context_rows(table, None, dev, pos,
                                    jnp.zeros((2, 4, 256)), 0, scale=1.0,
                                    value_width=128, interpret=True)


def _laguna_of_128_wide_heads(positions=48):
    from sitewhere_tpu.models import build_model

    return build_model(
        "laguna-stream", hidden_size=128, intermediate_size=128,
        moe_intermediate_size=128, shared_expert_intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, num_experts=8, num_experts_per_tok=2, vocab_size=64,
        num_experts_held=4, sliding_window=16, window=20,
        context_positions=positions, mlp_only_layers=[0],
        layer_types=["full_attention", "sliding_attention",
                     "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"],
        gating_types=["per_head"] * 3,
        num_attention_heads_per_layer=[4, 6, 4]), 3


def _olmo_of_128_wide_heads(positions=32):
    model = _linear_model(4, 16, 64, compute_dtype=jnp.bfloat16,
                          hidden_size=256, num_hidden_layers=2,
                          layer_types=[LINEAR, FULL],
                          context_positions=positions)
    return model, 1


def _ouro_of_128_wide_heads(positions=32):
    from sitewhere_tpu.models import build_model

    model = build_model(
        "ouro-stream", hidden_size=256, intermediate_size=256,
        num_hidden_layers=2, layer_types=["full_attention"] * 2,
        num_attention_heads=2, num_key_value_heads=2, head_dim=128,
        vocab_size=64, total_ut_steps=3, window=12,
        context_positions=positions)
    return model, model.slots


def _lfm2_of_64_wide_heads(positions=32):
    from sitewhere_tpu.models import build_model

    conv, full = "conv", "full_attention"
    return build_model(
        "lfm2-stream", hidden_size=256, intermediate_size=256,
        moe_intermediate_size=64, num_attention_heads=4,
        num_key_value_heads=2, vocab_size=64, num_experts=8,
        num_experts_per_tok=2, num_hidden_layers=4, num_dense_layers=1,
        layer_types=[conv, full, conv, full], window=16,
        context_positions=positions), 2


def _dsv3_of_a_128_wide_latent():
    from sitewhere_tpu.models import build_model

    return build_model(
        "dsv3-stream", hidden_size=256, intermediate_size=256,
        moe_intermediate_size=64, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=64,
        kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=32,
        v_head_dim=32, n_routed_experts=8, n_group=2, topk_group=1,
        num_experts_per_tok=2, vocab_size=64, mtp_modules=0, window=16,
        context_positions=32), 2


@pytest.mark.parametrize("build", [_laguna_of_128_wide_heads,
                                   _olmo_of_128_wide_heads,
                                   _ouro_of_128_wide_heads,
                                   _lfm2_of_64_wide_heads,
                                   _dsv3_of_a_128_wide_latent],
                         ids=["laguna-stream", "olmo-hybrid-stream",
                              "ouro-stream", "lfm2-stream", "dsv3-stream"])
def test_the_step_that_reads_contexts_at_rest_is_the_plain_step(
        build, monkeypatch):
    """The whole ring step of the models that read a context at rest,
    with the TPU's branch taken (the kernels in interpret mode) against
    the step as the CPU lowers it, in bfloat16 at heads of 128 (and of
    64, two to a lane tile, in `lfm2-stream`'s; one latent table of 256
    lanes that is both keys and values, in `dsv3-stream`'s): scores,
    every state leaf and the step's other numbers agree (both sides make
    the same bfloat16 products and sum them in float32, in another
    order), `ctx.at_rest` counts the live rows of every layer that
    attends over a stored context where the branch ran and 0 on the
    plain path, and the context tables are bit-equal but for what a
    differing sum's rounding put into a later layer's appended entry."""
    from sitewhere_tpu.scoring.stream import pad_rows, streaming_step

    model, layers = build()
    params = model.init(jax.random.PRNGKey(0))
    cap, frame, live = 9, 8, 5
    hist = np.random.default_rng(0).normal(size=(cap, model.cfg.window)
                                           ).astype(np.float32)
    seeded = jax.jit(model.warm_state)(params, jnp.asarray(hist),
                                       jnp.ones(hist.shape, bool))
    state = jax.tree.map(lambda leaf, rows: leaf.at[:cap].set(rows),
                         model.init_state(cap + 1), seeded)
    dev = np.concatenate([[0, 1, 4, 6, 8], pad_rows(cap, frame - live)]
                         ).astype(np.int32)
    v = np.linspace(-1, 1, frame).astype(np.float32)
    at = model.step_stats.index("ctx.at_rest")
    stats = len(model.step_stats)
    want_state, want = jax.jit(streaming_step(model))(params, state, dev, v)
    _interpreted(monkeypatch)
    _context_interpreted(monkeypatch)
    got_state, got = jax.jit(streaming_step(model))(params, state, dev, v)
    assert float(want[frame + at]) == 0
    assert float(got[frame + at]) == layers * live
    assert float(jnp.abs(want[:live]).max()) > 1.0
    np.testing.assert_allclose(got[:live], want[:live], atol=2e-2)
    others = [i for i in range(stats) if i != at and model.step_stats[i]
              not in ("state.in_place", "ctx.read_positions")]
    np.testing.assert_allclose(np.asarray(got[frame:])[others],
                               np.asarray(want[frame:])[others], rtol=1e-2)
    for name, leaf in want_state.items():
        np.testing.assert_allclose(
            np.asarray(got_state[name], np.float32),
            np.asarray(leaf, np.float32), atol=2e-2, err_msg=name)


# (what builds the model, each table its kernel reads a step at 128
# positions: (positions, the own entry beside, calls a step))
COPIED = {
    # two full layers of 128, a sliding one of 16 (one block, wrapped)
    "laguna-stream": (_laguna_of_128_wide_heads,
                      [(128, False, 2), (16, False, 1)]),
    "olmo-hybrid-stream": (_olmo_of_128_wide_heads, [(128, False, 1)]),
    # three passes of two layers, each (pass, layer) its own context
    "ouro-stream": (_ouro_of_128_wide_heads, [(128, True, 6)]),
    "lfm2-stream": (_lfm2_of_64_wide_heads, [(128, False, 2)]),
}


@pytest.mark.parametrize("name", COPIED)
def test_the_step_counts_the_positions_the_context_kernel_copied(
        name, monkeypatch):
    """`ctx.read_positions` among a step's numbers, with the TPU's branch
    taken (the kernels in interpret mode): over the live rows and every
    call of the context kernel, `ceil(len / B) * B`, `len` the row's
    `min(pos + 1, P)` (`min(pos, P)` where the own entry comes beside
    the table) and `B` the table's position block (64 of 128 positions;
    16 of 16); 0 as the CPU lowers the step."""
    from sitewhere_tpu.ops import context_kernel
    from sitewhere_tpu.scoring.stream import pad_rows, streaming_step

    build, tables = COPIED[name]
    model, _ = build(positions=128)
    params = model.init(jax.random.PRNGKey(0))
    cap, frame, at = 9, 8, [3, 63, 64, 100, 127]
    live = len(at)
    state = model.init_state(cap + 1)
    dev = np.concatenate([[0, 1, 4, 6, 8], pad_rows(cap, frame - live)]
                         ).astype(np.int32)
    state["pos"] = state["pos"].at[dev[:live]].set(np.asarray(at))
    v = np.linspace(-1, 1, frame).astype(np.float32)
    read = frame + model.step_stats.index("ctx.read_positions")
    _, want = jax.jit(streaming_step(model))(params, state, dev, v)
    _interpreted(monkeypatch)
    _context_interpreted(monkeypatch)
    _, got = jax.jit(streaming_step(model))(params, state, dev, v)
    assert [context_kernel.position_block(p) for p, _, _ in tables] == [
        64 if p == 128 else p for p, _, _ in tables]
    copied = 0
    for positions, own, calls in tables:
        block = 64 if positions == 128 else positions
        held = np.minimum(np.asarray(at) + (0 if own else 1), positions)
        copied += calls * int((np.maximum(-(-held // block), 1) * block).sum())
    assert float(want[read]) == 0
    assert float(got[read]) == copied
