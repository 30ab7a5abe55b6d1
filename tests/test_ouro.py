"""`ouro-stream` at a small size on the CPU, float32 products, seeded
weights: the program (models/ouro.py through scoring/stream.py's ring and
scoring/server.py's session) against the plain reference's full forward
pass (benchmarks/models/ouro_stream.py), the prefill form against the
decode form, each (pass, layer) context in its own lanes, and the turn.

Hidden 256, 2 heads of 128 on 2 key-value heads (a position's keys are
two lane tiles), 2 layers run 3 passes, vocabulary 512, contexts of 64
positions, seeded from windows of 16: six contexts a row, every part of
the loop, which is what a test has to compile.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import models
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model, ouro, seqblocks
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.scoring.stream import (
    StreamingRing,
    pad_rows,
    streaming_step,
)

# the same six devices' readings and host store as the siblings' tests
from tests.test_laguna import readings, store_with  # noqa: E402

reference = models.load("ouro-stream")

W, P, D = 16, 64, 6
FULL = "full_attention"
MC = dict(
    hidden_size=256, intermediate_size=512, num_hidden_layers=2,
    num_attention_heads=2, num_key_value_heads=2, head_dim=128,
    vocab_size=512, total_ut_steps=3, layer_types=[FULL] * 2,
    rms_norm_eps=1e-6, rope_theta=1000000, window=W, context_positions=P)
# float32 products on both sides, operand for operand: what is left is the
# order of float32 sums (1.4e-6 on scores of about 6 as it stands), so a
# tenth of a thousandth parts round-off from any change to the equations
# (the reference's own variants below move scores by 0.01 and more)
ROUND_OFF = 1e-4


def program(**over):
    return build_model("ouro-stream", compute_dtype=jnp.float32,
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


# (overrides, stored history, events, rows seeded again, whether the
# reference's scores have to differ from the first case's)
SEQUENCES = {
    "a_seeded_window_then_events": ({}, W + 4, 20, 0, False),
    "a_cold_fleet": ({}, 0, 20, 0, False),
    # 32 positions: the contexts fill after 16 events and again after 16
    # more, and the row is seeded again from its last 16 values, every
    # pass's contexts with it
    "contexts_that_fill_and_are_seeded_again": (
        {"context_positions": 32}, W + 4, 34, 2 * D, False),
    # what the loop's count changes, the reference follows
    "one_pass": ({"total_ut_steps": 1}, W + 4, 8, 0, True),
    "four_passes": ({"total_ut_steps": 4}, W + 4, 8, 0, True),
    "three_layers": ({"num_hidden_layers": 3, "layer_types": [FULL] * 3},
                     W + 4, 8, 0, True),
    # grouped heads: 4 query heads of 64 on 2 key-value heads of 128 is
    # no model; 4 of 128 on 2 is, with a wider query
    "grouped_query_heads": ({"num_attention_heads": 4}, W + 4, 8, 0, True),
}


@pytest.mark.parametrize("case", SEQUENCES)
def test_seeding_then_streaming_agrees_with_the_full_forward_pass(case):
    """The prefill form's loop over the window, then the decode form
    through the ring's contexts, a (pass, layer) a block of lanes,
    against the reference's full pass over each device's whole sequence,
    pass by pass, each pass's attention over that pass's keys and
    values."""
    over, history, ticks, reseeds, differs = SEQUENCES[case]
    mc = {**MC, **over}
    params = params_of(mc)
    hist, frames = readings(history, ticks)
    model = program(**over)
    served, ring = serve(model, params, hist, frames)
    fed = np.ones(frames.shape, bool)
    ref = reference.run(params, hist, frames, fed, mc, "float32")
    assert ring.reseeded == reseeds
    if history:
        assert 4.0 < ref.mean() < 8.0 and (ref > 0).all()
    else:
        assert (ref[:8] == 0).all() and (ref[8:] > 0).all()
    assert np.abs(served - ref).max() < ROUND_OFF
    if differs:
        # ...and the key changes the reference's numbers
        base = reference.run(params_of(MC), hist, frames, fed, MC, "float32")
        assert np.abs(ref - base).max() > 100 * ROUND_OFF
    assert model.slots == mc["total_ut_steps"] * mc["num_hidden_layers"]


def test_the_logits_agree_with_the_full_forward_pass():
    """Not only the score of the bin that arrived: the head's whole
    prediction the ring keeps (`hn`) against the reference's `h_U` at
    each device's last position, logits over all 512 bins."""
    from benchmarks.models.dsv3_stream import _event_tokens, _window_tokens

    params = params_of(MC)
    hist, frames = readings(W, 5)
    model = program()
    _, ring = serve(model, params, hist, frames)
    # the tokens of the same values, by the reference's own quantiser
    tokens, mean, var = _window_tokens(jnp.asarray(hist), vocab=512)
    tokens, n = [tokens], jnp.full(D, W, jnp.int32)
    for v in frames:
        tok, _, (mean, var, n) = _event_tokens(
            mean, var, n, jnp.asarray(v), jnp.ones(D, bool), vocab=512,
            window=W)
        tokens.append(tok[:, None])
    y = reference._Forward(MC, "float32").hidden(
        params, jnp.concatenate(tokens, 1))
    want = np.asarray(model._mm(reference._rms(y[:, -1], params["norm"],
                                               1e-6), params["head"]))
    got = np.asarray(model._mm(ring.state["hn"][:D], params["head"]))
    assert 0.3 < np.abs(want).max() < 10
    assert np.abs(got - want).max() < 1e-4


def test_the_turn_of_heads_side_by_side_is_the_halves_turn():
    """`turn_heads` on heads laid side by side is `rope_halves` on the
    heads, to the bit, and the reference's turn."""
    d = 128
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 3 * d), jnp.float32)
    cos, sin = seqblocks.rope_tables(9, d, 1e6)
    flat = np.asarray(ouro.turn_heads(x, jnp.asarray(cos), jnp.asarray(sin),
                                      d))
    heads = np.asarray(seqblocks.rope_halves(
        x.reshape(2, 9, 3, d), jnp.asarray(cos)[:, None, :],
        jnp.asarray(sin)[:, None, :])).reshape(x.shape)
    assert (flat == heads).all()
    want = np.asarray(reference._turn(x.reshape(2, 9, 3, d), 1e6))
    assert np.abs(flat - want.reshape(x.shape)).max() < 1e-5
    assert (flat[:, 0] == np.asarray(x)[:, 0]).all()


def test_seeding_a_window_is_seeding_its_head_and_stepping_its_tail():
    """The prefill form against the decode form on the same tokens: the
    state after seeding `c` tokens against the state after seeding the
    first `c - N` and stepping the last `N` through the ring: every
    leaf, every (pass, layer) block of the contexts, rows with a full
    window, a short one, one and none at all before the events. (A
    stored value IS its token here: the family's quantiser reads a
    window by the window's own statistics and an event by the running
    ones, so the same values are other tokens seeded than served.)"""
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
    values = rng.integers(0, 512, (D, window)).astype(np.float32)
    total = np.array([window, window - 3, n_events + 7, n_events + 2,
                      n_events + 1, n_events])

    def stored(count, upto):
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
    parts.load(stored(head, total), head)
    # a row seeded from nothing predicts nothing
    assert not np.asarray(parts.state["hn"])[D - 1].any()
    for k in range(n_events):
        parts.update_and_score(
            model, params, np.arange(D, dtype=np.int32),
            values[np.arange(D), window - n_events + k], 8)
    for name, want in whole.state.items():
        want, got = np.asarray(want)[:D], np.asarray(parts.state[name])[:D]
        if name in model.windows:       # a context holds `pos` positions
            keep = np.arange(want.shape[1])[None, :] < total[:, None]
            want, got = want * keep[..., None], got * keep[..., None]
            # every block holds something: all six contexts were written
            assert all(np.abs(want[..., s * 256:(s + 1) * 256]).max() > 0.01
                       for s in range(model.slots))
        err = np.abs(want.astype(np.float32) - got.astype(np.float32)).max()
        assert err < 2e-5 * max(1.0, np.abs(want).max()), (name, err)
    assert (np.asarray(whole.state["pos"])[:D] == total).all()
    assert sorted(whole.state) == ["count", "hn", "k", "mean", "pos", "v",
                                   "var"]


def test_a_row_that_fills_is_seeded_again_from_its_last_window():
    """`context_positions` 32: after 16 events every row is full, and
    its next event finds it seeded from its last 16 values, every
    context included: what a fresh ring holds after those values and
    that event (at the positions a row has reached)."""
    mc = {**MC, "context_positions": 32}
    model, params = program(context_positions=32), params_of(mc)
    hist, frames = readings(W, 17)
    served, ring = serve(model, params, hist, frames)
    assert ring.reseeded == D
    fresh = ring_of(model, params)
    fresh.load(frames[:16].T.copy(), np.full(D, W))
    fresh.update_and_score(model, params, np.arange(D, dtype=np.int32),
                           frames[16], 8)
    for name, want in fresh.state.items():
        want, got = np.asarray(want), np.asarray(ring.state[name])
        if name in model.windows:
            want, got = want[:, :W + 1], got[:, :W + 1]
        assert (want == got).all(), name
    assert (np.asarray(ring.state["pos"])[:D] == W + 1).all()


def test_each_pass_and_layer_writes_its_own_lanes_in_place():
    """The jitted step's outputs alias its donated state leaf for leaf;
    in the two context tables only `(row, pos)` changes, in every one of
    the six blocks of lanes, each block with its own entry; padding
    writes nothing; the scopes a profile shows the step by are there."""
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
    stats = dict(zip(model.step_stats, np.asarray(scores[8:])))
    # the CPU's step gathers its contexts: none read at rest
    assert stats["ctx.positions"] == W and stats["ctx.at_rest"] == 0
    assert stats["ctx.read_positions"] == 0
    layer = 4 * (4 * 256 * 256 + 3 * 256 * 512 + 4 * 256)
    assert stats["loop.weight_bytes"] == 3 * 2 * layer
    assert stats["ctx.attended_bytes"] == D * (W + 1) * 6 * 2 * 256 * 4
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry",
                        compiled.as_text()).group(1)
    assert aliases.count("may-alias") + aliases.count("must-alias") \
        == len(state)
    for name, leaf in state.items():
        now = np.asarray(leaf)
        changed = np.argwhere((now != before[name]).reshape(
            leaf.shape[0], -1).any(-1))[:, 0]
        assert set(changed) <= set(range(5, 5 + D)), name
        if name in model.windows:
            at = np.argwhere((now != before[name]).any(-1))
            assert {tuple(rc) for rc in at} == {(5 + i, W)
                                                for i in range(D)}, name
            entries = now[5:5 + D, W].reshape(D, model.slots, 256)
            assert (np.abs(entries).max(-1) > 0).all()
            # six blocks, six entries: no two passes wrote the same one
            assert all(np.abs(entries[:, s] - entries[:, t]).max() > 1e-3
                       for s in range(6) for t in range(s))
        elif name not in ("var", "count"):   # a full window's count stays
            assert set(changed) == set(range(5, 5 + D)), name
    text = compiled.as_text()
    for scope in ("ring_gather", "ctx_append", "ring_scatter", "loop_pass",
                  "loop_norm", "gqa_project", "attn_full", "dense_mlp",
                  "lm_head"):
        assert scope in text, scope


def test_the_steps_numbers_reach_the_registry_through_a_session(run):
    """A session over the ring: scores against the reference, and on the
    registry the context's positions, the bytes of layer weights the
    passes stream and of keys and values the equations read, no row read
    at rest on the CPU, no reseed."""
    params = params_of(MC)
    hist, frames = readings(W + 4, 10)
    model = program()

    async def main():
        store = store_with(hist, devices=D)
        metrics = MetricsRegistry()
        s = ScoringSession(model, store, metrics, ScoringConfig(
            buckets=(8,), threshold=7.5, score_dtype="float32", capacity=D),
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
        layer = 4 * (4 * 256 * 256 + 3 * 256 * 512 + 4 * 256)
        assert snap["scoring.loop.weight_bytes"].value \
            == snap["scoring.dispatches"].value * 3 * 2 * layer \
            == 10 * 15_753_216
        # a frame of six rows at positions W .. W + 9: (pos + 1) positions
        # of six contexts of keys and values, 256 float32 each
        assert snap["scoring.ctx.attended_bytes"].value \
            == D * sum(W + 1 + k for k in range(10)) * 6 * 2 * 256 * 4
        assert snap["scoring.ctx.at_rest_rows"].value == 0
        assert snap["scoring.ctx.read_positions"].value == 0
        assert snap["scoring.ctx.reseeds"].value == 0
        s.close()

    run(main())


def test_defaults_are_the_published_config():
    """The whole published config.json by default (the exit gate's
    threshold among it), nothing allocated."""
    model = build_model("ouro-stream")
    c = model.cfg
    assert (c.num_hidden_layers, c.hidden_size, c.intermediate_size,
            c.vocab_size, c.head_dim) == (48, 2048, 5632, 49152, 128)
    assert (c.num_attention_heads, c.num_key_value_heads, c.total_ut_steps,
            c.early_exit_threshold) == (16, 16, 4, 1)
    assert (c.rms_norm_eps, c.rope_theta, c.rope_scaling,
            c.tie_word_embeddings) == (1e-6, 1000000, None, False)
    assert c.layer_types == [FULL] * 48
    assert (model.passes, model.slots) == (4, 192)
    weights = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(weights)) \
        == 48 * 51_388_416 + 2 * 100_663_296 + 2048
    assert "early_exit_gate" not in weights


def test_configuration_the_model_cannot_compute_is_refused():
    with pytest.raises(ValueError, match="exits early"):
        program(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        program(tie_word_embeddings=True)
    with pytest.raises(ValueError, match="use_sliding_window"):
        program(use_sliding_window=True)
    with pytest.raises(ValueError, match="rope_scaling"):
        program(rope_scaling={"rope_type": "yarn", "factor": 4.0})
    with pytest.raises(ValueError, match="fewer than"):
        program(num_hidden_layers=3)
    with pytest.raises(ValueError, match="cannot compute"):
        program(layer_types=["sliding_attention"] * 2)
    with pytest.raises(ValueError, match="lane tiles"):
        program(head_dim=96)
    with pytest.raises(ValueError, match="fewer positions"):
        program(context_positions=8)
    with pytest.raises(ValueError, match="one pass"):
        program(total_ut_steps=0)
