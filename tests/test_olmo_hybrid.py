"""`olmo-hybrid-stream` at a small size on the CPU, float32 products,
seeded weights: the program (models/olmo_hybrid.py through
scoring/stream.py's ring and scoring/server.py's session) against the
plain reference's full forward pass
(benchmarks/models/olmo_hybrid_stream.py), the prefill form against the
decode form, and the bytes of the published widths.

Hidden 128, a full layer of 2 heads of 64, linear layers of 2 matrix
states of 32 x 64 (the two heads share one row of 128 lanes), MLP 256,
vocabulary 64, one period: every kind of layer, which is what a test
has to compile.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import models
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.scoring.stream import (
    StreamingRing,
    pad_rows,
    streaming_step,
)

# the same six devices' readings and host store as the sibling's tests
from tests.test_laguna import readings, store_with  # noqa: E402

reference = models.load("olmo-hybrid-stream")

W, P, D = 12, 40, 6
LINEAR, FULL = "linear_attention", "full_attention"
MC = dict(
    hidden_size=128, intermediate_size=256, num_hidden_layers=4,
    num_attention_heads=2, num_key_value_heads=2, vocab_size=64,
    linear_num_key_heads=2, linear_num_value_heads=2,
    linear_key_head_dim=32, linear_value_head_dim=64,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rms_norm_eps=1e-6, layer_types=[LINEAR] * 3 + [FULL], window=W,
    context_positions=P)
ROUND_OFF = 1e-5          # float32 round-off on scores of about 4


def program(**over):
    return build_model("olmo-hybrid-stream", compute_dtype=jnp.float32,
                       **{**MC, **over})


def params_of(mc):
    return reference.tenant_params(11, 0, mc)


def ring_of(model, params):
    ring = StreamingRing(model, capacity=D, initial_floor=D,
                         score_dtype="float32")
    ring.bind_params(params)
    return ring


def serve(model, params, hist, frames):
    """Seed from the stored windows (none where the fleet starts cold),
    then event by event. -> (scores [T, D], the ring)."""
    w = model.cfg.window
    ring = ring_of(model, params)
    if hist.shape[1]:
        ring.load(hist[:, -w:], np.full(D, w))
    out = [np.asarray(ring.update_and_score(
        model, params, np.arange(D, dtype=np.int32), v, 8))[:D]
        for v in frames]
    return np.stack(out), ring


# (overrides, stored history, events, rows seeded again)
SEQUENCES = {
    "a_seeded_window_then_events": ({}, W + 4, 20, 0),
    "a_cold_fleet": ({}, 0, 20, 0),
    # 24 positions: the full layer's context fills after 12 events and
    # again after 12 more, and the row is seeded again from its last 12
    # values, the matrix states and the conv's taps with it
    "a_context_that_fills_and_is_seeded_again": (
        {"context_positions": 24}, W + 4, 30, 2 * D),
    # eight layers, the kinds read from `layer_types` and not from a
    # period of four: full layers at 2 and 6
    "two_periods_read_from_layer_types": (
        {"num_hidden_layers": 8,
         "layer_types": [LINEAR, LINEAR, FULL, LINEAR] * 2}, W + 4, 6, 0),
    # beta in (0, 1): the reference follows the same key
    "no_negative_eigenvalues": ({"linear_allow_neg_eigval": False}, W + 4, 8,
                                0),
    # four heads of 32 values share a row of lanes
    "four_heads_to_a_row_of_lanes": (
        {"linear_num_key_heads": 4, "linear_num_value_heads": 4,
         "linear_key_head_dim": 16, "linear_value_head_dim": 32}, W + 4, 6,
        0),
}


@pytest.mark.parametrize("case", SEQUENCES)
def test_seeding_then_streaming_agrees_with_the_full_forward_pass(case):
    """The prefill form's scan, then the decode form through the ring's
    rows in turn and its context, against the reference's full pass over
    each device's whole sequence: a plain loop over positions from `S =
    0`, a left-padded conv, one masked softmax."""
    over, history, ticks, reseeds = SEQUENCES[case]
    mc = {**MC, **over}
    params = params_of(mc)
    hist, frames = readings(history, ticks)
    model = program(**over)
    served, ring = serve(model, params, hist, frames)
    ref = reference.run(params, hist, frames, np.ones(frames.shape, bool),
                        mc, "float32")
    assert ring.reseeded == reseeds
    if history:
        assert 3.0 < ref.mean() < 5.0 and (ref > 0).all()
    else:
        assert (ref[:8] == 0).all() and (ref[8:] > 0).all()
    assert np.abs(served - ref).max() < ROUND_OFF
    full = [l for l, kind in enumerate(mc["layer_types"]) if kind == FULL]
    assert sorted(model.windows) == sorted(
        f"{kv}{l}" for l in full for kv in "kv")


def test_the_factor_two_is_the_configurations_key():
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 128), jnp.float32)
    p = params_of(MC)["layer0"]
    _, _, _, wide = program()._gdn_project(p, x)
    _, _, _, narrow = program(linear_allow_neg_eigval=False)._gdn_project(p, x)
    assert (np.asarray(wide) == 2 * np.asarray(narrow)).all()
    assert 1.0 < np.asarray(wide).max() < 2.0 and np.asarray(narrow).max() < 1


def test_seeding_a_window_is_seeding_its_head_and_stepping_its_tail():
    """The prefill form against the decode form on the same tokens: the
    state after seeding `c` tokens against the state after seeding the
    first `c - N` and stepping the last `N` through the ring: every
    leaf, rows with a full window, a short one, fewer tokens than the
    conv has taps, and none at all before the events. (A stored value IS
    its token here: the family's quantiser reads a window by the
    window's own statistics and an event by the running ones, so the
    same values are other tokens seeded than served.)"""
    n_events = 5
    window = W + n_events
    mc = {**MC, "window": window}
    model, params = program(window=window), params_of(mc)

    def window_tokens(x, valid):
        count = valid.sum(1)
        first = (jnp.arange(window)[None, :] + (window - count)[:, None]) \
            % window
        return (jnp.take_along_axis(x.astype(jnp.int32), first, axis=1),
                count, jnp.zeros(x.shape[0]), jnp.ones(x.shape[0]))

    def arrive(params, rows, v):
        return v.astype(jnp.int32), jnp.zeros_like(v), {
            "mean": rows["mean"], "var": rows["var"],
            "count": jnp.minimum(rows["count"] + 1, window),
            "pos": rows["pos"] + 1}

    model._window_tokens, model._arrive = window_tokens, arrive
    rng = np.random.default_rng(3)
    values = rng.integers(0, 64, (D, window)).astype(np.float32)
    total = np.array([window, window - 3, n_events + 7, n_events + 2,
                      n_events + 1, n_events])

    def stored(count, upto):
        """Windows `[D, window]`, left-padded, of each row's first
        `count` values of its `upto`."""
        x = np.zeros((D, window), np.float32)
        for i in range(D):
            if count[i]:
                x[i, window - count[i]:] = values[
                    i, window - upto[i]:window - upto[i] + count[i]]
        return x

    whole = ring_of(model, params)
    whole.load(stored(total, total), total)
    parts = ring_of(model, params)
    head = total - n_events
    assert list(head[-3:]) == [2, 1, 0]
    parts.load(stored(head, total), head)
    for k in range(n_events):
        parts.update_and_score(
            model, params, np.arange(D, dtype=np.int32),
            values[np.arange(D), window - n_events + k], 8)
    for name, want in whole.state.items():
        want, got = np.asarray(want)[:D], np.asarray(parts.state[name])[:D]
        if name in model.windows:       # a context holds `pos` positions
            keep = np.arange(want.shape[1])[None, :] < total[:, None]
            want, got = want * keep[..., None], got * keep[..., None]
        assert want.shape == got.shape
        err = np.abs(want.astype(np.float32) - got.astype(np.float32)).max()
        assert err < 2e-5 * max(1.0, np.abs(want).max()), (name, err)
    assert (np.asarray(whole.state["pos"])[:D] == total).all()
    assert np.abs(np.asarray(whole.state["s0"])).max() > 0
    # a row that has seen nothing rests at zero (its context holds what
    # the prefill made of the padding, past `pos`, where nothing reads)
    empty = ring_of(model, params)
    empty.load(stored(head, total), head)
    for name in ("s0", "s2", "c1", "hn"):
        assert not np.asarray(empty.state[name])[D - 1].any(), name


def test_a_row_that_fills_is_seeded_again_from_its_last_window():
    """`context_positions` 24: after 12 events every row is full, and
    its next event finds it seeded from its last 12 values, matrix
    states included: what a fresh ring holds after those values and
    that event."""
    mc = {**MC, "context_positions": 24}
    model, params = program(context_positions=24), params_of(mc)
    hist, frames = readings(W, 13)
    served, ring = serve(model, params, hist, frames)
    assert ring.reseeded == D
    fresh = ring_of(model, params)
    fresh.load(frames[:12].T.copy(), np.full(D, W))
    fresh.update_and_score(model, params, np.arange(D, dtype=np.int32),
                           frames[12], 8)
    for name, want in fresh.state.items():
        assert (np.asarray(want) == np.asarray(ring.state[name])).all(), name
    assert (np.asarray(ring.state["pos"])[:D] == W + 1).all()


def test_matrix_states_are_written_in_place_in_their_turn():
    """The jitted step's outputs alias its donated state leaf for leaf;
    only the rows named change in a matrix state, only `(row, pos)` in a
    context; padding writes nothing; the scopes a profile shows the step
    by are there."""
    import re

    model, params = program(), params_of(MC)
    hist, frames = readings(W, 1)
    step = jax.jit(streaming_step(model), donate_argnums=(1,))
    cap = 20
    state = jax.device_put(model.init_state(cap + 1))
    seeded = jax.jit(model.warm_state)(params, jnp.asarray(hist),
                                       jnp.ones((D, W), bool))
    state = jax.tree.map(lambda leaf, rows: leaf.at[5:5 + D].set(rows),
                         state, seeded)
    before = jax.tree.map(np.asarray, state)
    dev = np.concatenate([np.arange(5, 5 + D, dtype=np.int32),
                          pad_rows(cap, 8 - D)])
    v = np.zeros(8, np.float32)
    v[:D] = frames[0]
    compiled = step.lower(params, state, dev, v).compile()
    state, scores = compiled(params, state, dev, v)
    assert scores.shape == (8 + len(model.step_stats),)
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry",
                        compiled.as_text()).group(1)
    assert aliases.count("may-alias") + aliases.count("must-alias") \
        == len(state)
    for name, leaf in state.items():
        changed = np.argwhere((np.asarray(leaf) != before[name]).reshape(
            leaf.shape[0], -1).any(-1))[:, 0]
        assert set(changed) <= set(range(5, 5 + D)), name
        if name in model.windows:
            at = np.argwhere((np.asarray(leaf) != before[name]).any(-1))
            assert {tuple(rc) for rc in at} == {(5 + i, W)
                                                for i in range(D)}, name
        elif name not in ("var", "count"):   # a full window's count stays
            assert set(changed) == set(range(5, 5 + D)), name
    text = compiled.as_text()
    for scope in ("ring_gather", "ctx_append", "ring_scatter", "gdn_project",
                  "gdn_conv", "gdn_state", "gdn_out", "attn_full",
                  "dense_mlp", "lm_head"):
        assert scope in text, scope


def test_a_turn_through_update_is_the_read_cell_and_write_it_replaced(
        monkeypatch):
    """`RowsInTurn.update` on the CPU is the plain path: the scores, the
    step's numbers and every leaf of the next state are bit-equal to the
    step that read a layer's rows, ran `_gdn_cell` on them and wrote
    them (the form before the state was updated where it rests), padding
    and all; and the plain path counts no row as updated in place."""
    model, params = program(), params_of(MC)
    hist, frames = readings(W, 2)
    cap = 11
    state = model.init_state(cap + 1)
    seeded = jax.jit(model.warm_state)(params, jnp.asarray(hist),
                                       jnp.ones((D, W), bool))
    state = jax.tree.map(lambda leaf, rows: leaf.at[3:3 + D].set(rows),
                         state, seeded)
    dev = np.concatenate([np.arange(3, 3 + D, dtype=np.int32),
                          pad_rows(cap, 8 - D)])

    def two_steps():
        step, at, out = jax.jit(streaming_step(model)), state, []
        for k in range(2):
            v = np.zeros(8, np.float32)
            v[:D] = frames[k]
            at, scores = step(params, at, dev, v)
            out.append(np.asarray(scores))
        return jax.tree.map(np.asarray, at), out

    got_state, got = two_steps()

    def as_before(self, p, x, state, taps):
        z, gate, alpha, beta = self._gdn_project(p, x)
        o, s, c1, held = self._gdn_cell(
            p, state.read(x), taps.read(x).reshape(x.shape[0], -1), z,
            alpha, beta)
        x = state.write(s, self._gdn_out(p, x, o, gate))
        x = taps.write(c1.reshape((-1,) + self._taps_shape), x)
        return x, alpha, held, jnp.int32(0)

    monkeypatch.setattr(type(model), "_linear_decode", as_before)
    want_state, want = two_steps()
    assert model.step_stats[-3:] == ("state.in_place", "ctx.at_rest",
                                     "ctx.read_positions")
    for a, b in zip(got, want):
        assert (a == b).all() and not a[-3:].any()
    for name, leaf in want_state.items():
        assert (got_state[name] == leaf).all(), name


def test_the_steps_numbers_reach_the_registry_through_a_session(run):
    """A session over the ring: scores against the reference, and on the
    registry the context's positions, the mean decay, the largest
    magnitude a step left in a state, and the bytes the dispatches
    rewrote whole."""
    params = params_of(MC)
    hist, frames = readings(W + 4, 10)
    model = program()

    async def main():
        store = store_with(hist, devices=D)
        metrics = MetricsRegistry()
        s = ScoringSession(model, store, metrics, ScoringConfig(
            buckets=(8,), threshold=4.5, score_dtype="float32", capacity=D),
            params=params)
        await s.warmup_async()
        served = []
        for k, v in enumerate(frames):
            batch = MeasurementBatch(
                BatchContext(tenant_id="t"), np.arange(D, dtype=np.uint32),
                np.zeros(D, np.uint16), v,
                np.full(D, (hist.shape[1] + k) * 60.0, np.float64))
            store.append_measurements(batch)
            s.admit(batch)
            served.append((await s.flush()).score)
        ref = reference.run(params, hist, frames, np.ones(frames.shape, bool),
                            MC, "float32")
        assert np.abs(np.stack(served) - ref).max() < ROUND_OFF
        snap = dict(metrics._metrics)
        assert snap["scoring.ctx.positions"].count == 10
        assert snap["scoring.ctx.positions"]._max == W + 9
        decay = snap["scoring.state.decay"]
        assert decay.count == 10 and 0.2 < decay.sum / 10 < 1.0
        absmax = snap["scoring.state.absmax"]
        assert absmax.count == 10 and 0 < absmax._max < 1.0
        # the CPU's step is the plain path: no row updated where it rests
        assert snap["scoring.state.in_place_rows"].value == 0
        # ...and no context read where it rests: its rows are gathered
        assert snap["scoring.ctx.at_rest_rows"].value == 0
        assert snap["scoring.ctx.read_positions"].value == 0
        assert snap["scoring.ctx.reseeds"].value == 0
        # 6 live rows of: three states of 32 x 128 float32, three of
        # taps 3 x 256 float32 (the products' type here), hn, 4 scalars
        row = 3 * 32 * 128 * 4 + 3 * 768 * 4 + 128 * 4 + 16
        assert s.ring.row_bytes == row
        assert snap["scoring.state.rewritten_bytes"].value == 10 * D * row
        s.close()

    run(main())


def test_defaults_are_the_published_config_and_its_bytes():
    """The whole published config.json by default, and ISSUE 35's bytes
    at those widths, nothing allocated: 3.21 GB of weights at a depth of
    four, 12.75 MB a device."""
    model = build_model("olmo-hybrid-stream")
    c = model.cfg
    assert (c.num_hidden_layers, c.hidden_size, c.intermediate_size,
            c.vocab_size) == (32, 3840, 11008, 100352)
    assert model.kinds == ([LINEAR] * 3 + [FULL]) * 8
    assert (c.linear_num_value_heads, c.linear_key_head_dim,
            c.linear_value_head_dim, c.linear_conv_kernel_dim) == (30, 96,
                                                                   192, 4)
    assert c.head_dim == 128 and c.rope_parameters == {"rope_theta": None}
    cut = build_model("olmo-hybrid-stream", num_hidden_layers=4)
    weights = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    assert round(sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(weights)) / 1e9, 2) == 3.21
    row = jax.eval_shape(lambda: cut.init_state(1))
    assert row["s0"].shape == (1, 15, 96, 384)        # two heads of 192
    assert row["c0"].shape == (1, 270, 128)
    assert round(sum(x.size * x.dtype.itemsize
                     for x in row.values()) / 1e6, 2) == 12.75
    # the family's draws: a decay in (0, 1) a head, steps of 0.001 to 0.1
    p = jax.jit(program().init)(jax.random.PRNGKey(1))["layer0"]
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert (0.001 <= dt).all() and (dt <= 0.1001).all()
    assert (np.asarray(jnp.exp(p["A_log"])) < 16.0).all()


def test_configuration_the_model_cannot_compute_is_refused():
    with pytest.raises(ValueError, match="rope_theta"):
        program(rope_parameters={"rope_theta": 10000})
    with pytest.raises(ValueError, match="fewer than"):
        program(num_hidden_layers=5)
    with pytest.raises(ValueError, match="lane tiles"):
        program(hidden_size=96, num_attention_heads=2)
    with pytest.raises(ValueError, match="lane tiles"):
        program(linear_num_value_heads=3, linear_num_key_heads=3)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        program(num_key_value_heads=1)
    with pytest.raises(ValueError, match="cannot compute"):
        program(layer_types=["sliding_attention"] * 4)
