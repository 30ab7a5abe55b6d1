"""Parity tests for the Pallas fused-window LSTM kernel
(ops/lstm_kernel.py) in interpret mode — the kernel's math must match
the lax.scan reference path it replaces on TPU.

Interpret mode executes the kernel's memory/grid semantics in the
Pallas interpreter on CPU, so these tests pin correctness everywhere;
Mosaic's own compile and on-chip parity are checked by chip_smoke.py
phase B.
"""

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


@pytest.mark.parametrize("hidden, inter", [(7168, 2048), (3072, 1024)],
                         ids=["dsv3_widths", "laguna_widths"])
@pytest.mark.parametrize("held, tile", [(1, 64), (2, 32), (3, 32)])
def test_expert_kernel_matches_the_plain_products_interpret(held, tile,
                                                            hidden, inter):
    """ops/expert_kernel.py at each served model's published widths
    (hidden 7168, intermediate 2048 in its sixteen blocks of 128; hidden
    3072, intermediate 1024 in eight) over one, two and
    three experts of few rows, against the three products
    `Dsv3StreamModel._mlp` makes of each and one scatter-add: bf16
    operands, f32 sums, `silu * up` rounded to bf16 once, the weight
    applied in f32, a token's experts summed in f32. The down product is
    summed block by block, so the two differ by the order of a float32
    sum. A run's rows past its count add nothing, whatever their weight.
    (Memory a kernel never wrote reads NaN in interpret mode.)"""
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.ops.expert_kernel import expert_tiles, fits

    tokens = 72
    # a frame of either cell fits, a seeding call's tokens do not
    assert fits(1024, 7168, 2048, 128) and not fits(2048, 7168, 2048, 128)
    assert fits(256, 3072, 1024, 128) and not fits(4224, 3072, 1024, 128)
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
    counts = np.asarray([tile, 0, 5][:held], np.int32)
    x = jax.random.normal(next(keys), (tokens, hidden)).astype(jnp.bfloat16)
    wts = jax.random.uniform(next(keys), (held * tile,))
    got = jax.jit(lambda ex, xs, rows, wts, counts: expert_tiles(
        ex, xs, rows, wts, counts, tokens, interpret=True))(
            experts, x[rows], rows, wts, counts)
    model = build_model("dsv3-stream", num_hidden_layers=1, mtp_modules=0)
    real = (np.arange(tile)[None, :] < counts[:, None]).reshape(-1)
    ys = jnp.concatenate([
        model._mlp(expert, x[rows[e * tile:(e + 1) * tile]])
        for e, expert in enumerate(experts)]) * (wts * real)[:, None]
    want = jnp.zeros((tokens, hidden), jnp.float32).at[rows].add(ys)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert 0.1 < scale < 10
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
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


def test_state_kernel_takes_float32_rows_of_whole_tiles_that_vmem_holds():
    """`fits` reads the leaf's shape and dtype: the published row and the
    tests' small ones; not a bfloat16 leaf, a row of keys that is no
    whole sublane tile, lanes that are no whole lane tile, nor a row
    four of which pass the VMEM the call asks for; and `update_rows`
    refuses what `fits` does not take."""
    from sitewhere_tpu.ops import state_kernel

    fits = state_kernel.fits
    assert fits((769, 15, 96, 384), jnp.float32)
    assert state_kernel.vmem_bytes((769, 15, 96, 384)) < 10 << 20
    assert fits((7, 2, 16, 128), jnp.float32)
    assert not fits((769, 15, 96, 384), jnp.bfloat16)
    assert not fits((769, 15, 92, 384), jnp.float32)
    assert not fits((769, 15, 96, 192), jnp.float32)
    assert not fits((769, 30, 96, 384), jnp.float32)
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
    assert model.step_stats[-1] == "state.in_place"
    assert float(want[-1]) == 0
    assert float(got[-1]) == (3 * live if in_place else 0)
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5)
    np.testing.assert_allclose(got[-stats:-1], want[-stats:-1], rtol=1e-6)
    for name, leaf in want_state.items():
        np.testing.assert_allclose(got_state[name], leaf, atol=1e-5,
                                   err_msg=name)
