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
