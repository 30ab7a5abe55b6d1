"""`lfm2-stream` at a small size on the CPU, float32 products, seeded
weights: the program (models/lfm2.py through scoring/stream.py's ring
and scoring/server.py's session) against the plain reference's full
forward pass (benchmarks/models/lfm2_stream.py), the prefill form
against the decode form, the share an expert layer WITHOUT a shared
expert holds, and the bytes of the published widths.

Hidden 256, 4 query heads of 64 on 2 key-value heads (a position's keys
are one lane tile), four layers (conv with the dense MLP, conv,
attention, conv, the last three with 8 experts of 64, 2 a token),
vocabulary 64: every kind of layer, which is what a test has to compile.
"""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import models
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model, seqblocks
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.scoring.stream import (
    StreamingRing,
    pad_rows,
    streaming_step,
)

# the same six devices' readings and host store as the sibling's tests
from tests.test_laguna import _lowered_step, readings, store_with  # noqa: E402

reference = models.load("lfm2-stream")

W, P, D = 12, 40, 6
CONV, FULL = "conv", "full_attention"
MC = dict(
    hidden_size=256, intermediate_size=256, moe_intermediate_size=64,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=64, num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, use_expert_bias=True,
    norm_topk_prob=True, routed_scaling_factor=1,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    layer_types=[CONV, CONV, FULL, CONV], tie_embedding=True, window=W,
    context_positions=P)
ROUND_OFF = 1e-5          # float32 round-off on scores of about 4


def program(**over):
    return build_model("lfm2-stream", compute_dtype=jnp.float32,
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
    # 24 positions: the attention layer's context fills after 12 events
    # and again after 12 more, and the row is seeded again from its last
    # 12 values, the conv states with it
    "a_context_that_fills_and_is_seeded_again": (
        {"context_positions": 24}, W + 4, 30, 2 * D, False),
    # twelve layers, the kinds read from `layer_types` and the dense
    # layers from `num_dense_layers`, not from a period or a constant:
    # attention at 1, 5 and 7, an expert layer from 2 on
    "twelve_layers_read_from_layer_types_two_of_them_dense": (
        {"num_hidden_layers": 12, "num_dense_layers": 2,
         "layer_types": [CONV, FULL, CONV, CONV, CONV, FULL, CONV, FULL,
                         CONV, CONV, CONV, CONV]}, W + 4, 6, 0, True),
    # what the router's three keys change, the reference follows
    "no_selection_bias": ({"use_expert_bias": False}, W + 4, 8, 0, True),
    "kept_weights_as_they_are": ({"norm_topk_prob": False}, W + 4, 8, 0,
                                 True),
    "a_scaling_factor": ({"routed_scaling_factor": 2.5}, W + 4, 8, 0, True),
    # four taps: three past inputs a row, `[rows, 6, 128]`
    "four_taps": ({"conv_L_cache": 4}, W + 4, 8, 0, True),
}


@pytest.mark.parametrize("case", SEQUENCES)
def test_seeding_then_streaming_agrees_with_the_full_forward_pass(case):
    """The prefill form's one convolution over the window, then the
    decode form through the ring's rows in turn and its context, against
    the reference's full pass over each device's whole sequence: a
    left-padded depthwise convolution, one masked softmax, every expert
    over every token."""
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
        assert 3.0 < ref.mean() < 5.5 and (ref > 0).all()
    else:
        assert (ref[:8] == 0).all() and (ref[8:] > 0).all()
    assert np.abs(served - ref).max() < ROUND_OFF
    if differs:
        # ...and the key changes the reference's numbers: under the
        # weights it CAN share with the published rule, the scores move
        base = reference.run(params_of(MC), hist, frames, fed, MC, "float32")
        assert np.abs(ref - base).max() > 100 * ROUND_OFF
    full = [l for l, kind in enumerate(mc["layer_types"]) if kind == FULL]
    assert sorted(model.windows) == sorted(
        f"{kv}{l}" for l in full for kv in "kv")
    assert model.at_rest == tuple(model.windows)
    assert model.dense == [l < mc["num_dense_layers"]
                           for l in range(mc["num_hidden_layers"])]


def test_the_denominators_one_millionth_is_the_published_rule():
    """Weights are the chosen `s` over (their sum + 1e-6): a router whose
    sigmoids are small shows it, the reference's rule agrees, and the
    plain sum (the other two models' rule) does not."""
    model = program()
    x = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (16, 256))
    # logits of about -10: sigmoids of 5e-5, two of them sum to 1e-4
    p = {"w": jax.random.normal(jax.random.PRNGKey(5), (8, 256)) * 0.01
         - 10.0 / 256, "bias": -1e-6 * jnp.arange(8, dtype=jnp.float32)}
    idx, w = model.route(p, x)
    want = np.asarray(reference.routing_weights(p, x, MC))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=1)
    assert 0 < want.max() < 0.9 and (want > 0).sum(1).tolist() == [2] * 16
    assert np.abs(got - want).max() < 1e-6 < 1e-3 < 1 - want.sum(1).max()
    model.experts = dataclasses.replace(model.experts, sum_eps=0.0)
    _, plain = model.route(p, x)
    assert np.abs(np.asarray(plain).sum(1) - 1).max() < 1e-6
    assert np.abs(np.asarray(w).sum(1) - 1).max() > 1e-3
    # the selection bias takes part in the choice only
    _, unbiased = model.route({"w": p["w"]}, x)
    assert np.asarray(unbiased).shape == (16, 2)


def test_the_halves_turn_is_the_references_and_not_the_pairs_turn():
    d = 64
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 4, d), jnp.float32)
    cos, sin = seqblocks.rope_tables(9, d, 1e6)
    at = (jnp.asarray(cos)[None, :, None, :], jnp.asarray(sin)[None, :, None, :])
    halves = np.asarray(seqblocks.rope_halves(x, *at))
    want = np.asarray(reference._turn(x, 1e6))
    assert np.abs(halves - want).max() < 1e-6
    pairs = np.asarray(seqblocks.rope(x, *at))
    assert np.abs(pairs - want).max() > 0.1
    # position 0 turns nothing; a turn keeps a pair's length
    assert (halves[:, 0] == np.asarray(x)[:, 0]).all()
    assert np.abs((halves ** 2).sum(-1) - (np.asarray(x) ** 2).sum(-1)).max() \
        < 1e-4
    # the same turn under a permutation of the columns: (2i, 2i + 1)
    # of the pairs' layout is (i, i + d / 2) of the halves'
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    assert np.abs(np.asarray(seqblocks.rope(
        x[..., np.argsort(perm)], *at))[..., perm] - halves).max() < 1e-6


def test_four_shares_of_an_expert_layer_without_a_shared_expert_add_up():
    """The guide's share test on the layer this model brings: 64 routed
    experts, none shared; with 16 held from 0, 16, 32 and 48 on, the
    four shares' outputs add up to the whole layer's (64 held) and to
    the reference's, which loops over every expert."""
    mc = {**MC, "num_experts": 64, "num_experts_per_tok": 4,
          "num_hidden_layers": 2, "layer_types": [CONV, CONV]}
    p = params_of(mc)["layer1"]
    n = jax.random.normal(jax.random.PRNGKey(7), (24, 256), jnp.float32)
    live = jnp.ones(24, bool)
    whole, counts = program(**mc)._ffn(p, n, live)
    assert "shared" not in p and int(counts.sum()) == 24 * 4
    want = np.asarray(reference.expert_layer(p, n, mc, "float32"))
    assert np.abs(np.asarray(whole) - want).max() < 1e-5
    total = np.zeros_like(want)
    for first in (0, 16, 32, 48):
        share = {**p, "experts": {f"e{e}": p["experts"][f"e{first + e}"]
                                  for e in range(16)}}
        held = {**mc, "first_expert": first, "num_experts_held": 16}
        out, counts = program(**held)._ffn(share, n, live)
        assert counts.shape == (16,)
        ref = np.asarray(reference.expert_layer(share, n, held, "float32"))
        assert np.abs(np.asarray(out) - ref).max() < 1e-5
        total += np.asarray(out)
    assert np.abs(want).max() > 0.01
    assert np.abs(total - want).max() < 1e-5


def test_seeding_a_window_is_seeding_its_head_and_stepping_its_tail():
    """The prefill form against the decode form on the same tokens: the
    state after seeding `c` tokens against the state after seeding the
    first `c - N` and stepping the last `N` through the ring: every
    leaf, rows with a full window, a short one, fewer tokens than the
    conv has taps, one and none at all before the events. (A stored
    value IS its token here: the family's quantiser reads a window by
    the window's own statistics and an event by the running ones, so the
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
    # a row seeded from one value keeps it as the newer of its two past
    # inputs and zeros as the older; one seeded from nothing keeps zeros
    taps = np.asarray(parts.state["c0"]).reshape(D + 1, 2, 256)
    assert not taps[D - 1].any() and not taps[D - 2, 0].any()
    assert taps[D - 2, 1].any() and taps[D - 3].all(-1).all()
    for name in ("c0", "c3", "hn"):
        assert not np.asarray(parts.state[name])[D - 1].any(), name
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
    assert sorted(whole.state) == sorted(
        ["mean", "var", "count", "pos", "hn", "c0", "c1", "k2", "v2", "c3"])


def test_a_row_that_fills_is_seeded_again_from_its_last_window():
    """`context_positions` 24: after 12 events every row is full, and
    its next event finds it seeded from its last 12 values, conv states
    included: what a fresh ring holds after those values and that
    event."""
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


def test_conv_states_and_contexts_are_written_in_place_in_their_turn():
    """The jitted step's outputs alias its donated state leaf for leaf;
    only the rows named change in a conv state, only `(row, pos)` in a
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
    # 6 live rows x 2 a token x 3 expert layers, every pair held; the
    # CPU's step gathers its contexts: none read at rest
    assert list(np.asarray(scores[8:])[[0, 1, 3, 5, 6]]) == [36, 36, W, 0, 0]
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
    # the older of a row's two past inputs is what was the newer
    for name in ("c0", "c1", "c3"):
        was = before[name][5:5 + D].reshape(D, 2, 256)
        now = np.asarray(state[name])[5:5 + D].reshape(D, 2, 256)
        assert (now[:, 0] == was[:, 1]).all() and (now[:, 1] != was[:, 1]).any()
    text = compiled.as_text()
    for scope in ("ring_gather", "ctx_append", "ring_scatter", "conv_project",
                  "conv_taps", "conv_out", "gqa_project", "attn_full",
                  "moe_route", "moe_experts", "dense_mlp", "lm_head"):
        assert scope in text, scope


def test_the_steps_numbers_reach_the_registry_through_a_session(run):
    """A session over the ring: scores against the reference, and on the
    registry the context's positions, the pairs routed (every one held),
    the bytes of expert leaves a dispatch's step streams, and the bytes
    the dispatches rewrote whole."""
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
        assert snap["scoring.moe.assignments"].value == 10 * D * 2 * 3
        assert snap["scoring.moe.assignments_held"].value \
            == snap["scoring.moe.assignments"].value
        assert snap["scoring.moe.runs_one_tile"].value == 10 * 8 * 3
        # three expert layers of 8 experts of 3 x 256 x 64 float32
        assert snap["scoring.moe.weight_bytes"].value \
            == snap["scoring.dispatches"].value * 3 * 8 * 3 * 256 * 64 * 4 \
            == 10 * 4_718_592
        assert snap["scoring.ctx.at_rest_rows"].value == 0
        assert snap["scoring.ctx.read_positions"].value == 0
        assert snap["scoring.ctx.reseeds"].value == 0
        # 6 live rows of: three conv states of 2 x 256 float32 (the
        # products' type here), hn, 4 scalars
        row = 3 * 512 * 4 + 256 * 4 + 16
        assert s.ring.row_bytes == row
        assert snap["scoring.state.rewritten_bytes"].value == 10 * D * row
        s.close()

    run(main())


def test_held_expert_bytes_are_read_off_the_checkpoints_layout():
    """`scoring.moe.weight_bytes` a dispatch, for the models that were
    there: a layer's `experts` leaves and nothing else (not a shared
    expert, not a module's block that no step runs), 0 without them."""
    from sitewhere_tpu.models.seqblocks import held_expert_bytes
    from tests.test_dsv3 import MC as DSV3
    from tests.test_olmo_hybrid import MC as OLMO

    dsv3 = build_model("dsv3-stream", **DSV3)
    layers = DSV3["num_hidden_layers"] - DSV3["first_k_dense_replace"]
    assert held_expert_bytes(dsv3) == layers * dsv3.experts.held * 2 * 3 \
        * DSV3["hidden_size"] * DSV3["moe_intermediate_size"] > 0
    assert held_expert_bytes(build_model("olmo-hybrid-stream", **OLMO)) == 0
    assert held_expert_bytes(build_model("lstm-stream")) == 0


def test_defaults_are_the_published_config_and_its_bytes():
    """The whole published config.json by default, and ISSUE 39's bytes
    at those widths, nothing allocated: 4,025,293,440 parameters at a
    depth of eight, 2,150,416 B a device."""
    model = build_model("lfm2-stream")
    c = model.cfg
    assert (c.num_hidden_layers, c.hidden_size, c.intermediate_size,
            c.vocab_size, c.moe_intermediate_size) == (40, 2048, 11776, 65536,
                                                       1536)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.conv_L_cache, c.conv_bias) == (32, 8, 64, 3, False)
    assert (c.num_dense_layers, c.num_experts, c.num_experts_per_tok,
            c.use_expert_bias, c.norm_topk_prob, c.routed_scaling_factor,
            c.norm_eps) == (2, 64, 4, True, True, 1, 1e-5)
    assert c.rope_parameters == {"rope_theta": 1000000,
                                 "rope_type": "default"}
    assert model.kinds == [CONV, CONV, FULL] + [CONV, CONV, CONV, FULL] * 9 \
        + [CONV]
    assert (model.kinds.count(CONV), model.kinds.count(FULL)) == (30, 10)
    assert model.experts == seqblocks.Experts(
        routed=64, held=64, first=0, per_token=4, scale=1.0,
        scoring="sigmoid", normed=True, sum_eps=1e-6)
    cut = build_model("lfm2-stream", num_hidden_layers=8)
    weights = jax.eval_shape(cut.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(weights)) == 4_025_293_440
    assert "head" not in weights            # one matrix, tied
    per_layer = [sum(x.size for x in jax.tree.leaves(weights[f"layer{l}"]))
                 for l in range(8)]
    assert per_layer == [89_139_200] * 2 + [614_600_896] + [620_898_368] * 3 \
        + [614_600_896, 620_898_368]
    row = jax.eval_shape(lambda: cut.init_state(1))
    assert row["c0"].shape == (1, 32, 128) and row["c0"].dtype == jnp.bfloat16
    assert row["k2"].shape == row["v6"].shape == (1, 512, 512)
    assert sorted(cut.windows) == ["k2", "k6", "v2", "v6"]
    assert sum(x.size * x.dtype.itemsize for x in row.values()) == 2_150_416
    # the builder's draw: three taps a channel whose squares sum to 1 on
    # average, so the taps' sum keeps its input's scale
    taps = np.asarray(jax.jit(program().init)(jax.random.PRNGKey(1))[
        "layer0"]["conv"], np.float32)
    assert taps.shape == (3, 256) and 0.8 < (taps ** 2).sum(0).mean() < 1.2


def test_configuration_the_model_cannot_compute_is_refused():
    with pytest.raises(ValueError, match="conv_bias"):
        program(conv_bias=True)
    with pytest.raises(ValueError, match="tie_embedding"):
        program(tie_embedding=False)
    with pytest.raises(ValueError, match="plain rope"):
        program(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})
    with pytest.raises(ValueError, match="fewer than"):
        program(num_hidden_layers=5)
    with pytest.raises(ValueError, match="cannot compute"):
        program(layer_types=["sliding_attention"] * 4)
    with pytest.raises(ValueError, match="lane tiles"):
        program(hidden_size=128, num_attention_heads=4)
    with pytest.raises(ValueError, match="past num_experts"):
        program(first_expert=6, num_experts_held=4)
    with pytest.raises(ValueError, match="fewer positions"):
        program(context_positions=8)


# sha256 of `olmo-hybrid-stream`'s lowered ring step (StableHLO text) at
# PR 38's tree, the parent of the PR that taught models/seqblocks.py an
# expert layer without a shared expert, a denominator with the published
# 1e-6, the halves turn and a tied head: tests/test_olmo_hybrid.py's size,
# the same function, there, on the same arguments. (The other models'
# pins are tests/test_laguna.py's: `lstm-stream`'s holds too; the two
# models with held experts moved with `routed`'s overflow loop, and their
# pins there say so.) Recorded anew when the step came to return one
# number more, `ctx.read_positions` (0 on the CPU: a constant, an add, a
# convert and a broadcast more, no other line).
OLMO_PARENTS_STEPS = {
    "float32": (
        "52c99a085594729227c11bb1845e4aaddaecb84f51542a32b540e2ab5750b7dd"),
    "bfloat16": (
        "972aaf8b1eb5ccfed20487f6de8eff8fb93b72dd6a4483b11bef947f2e86d48e"),
}


@pytest.mark.parametrize("products", OLMO_PARENTS_STEPS)
def test_olmo_hybrids_step_lowers_to_the_parents_text(products):
    from tests.test_olmo_hybrid import MC as OLMO

    over = {"compute_dtype": jnp.float32} if products == "float32" else {}
    text = _lowered_step(build_model("olmo-hybrid-stream", **over, **OLMO),
                         41, 16, jnp.float32)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == OLMO_PARENTS_STEPS[products]
