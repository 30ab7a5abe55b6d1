"""`laguna-stream` at a small size on the CPU, float32 products, seeded
weights: the program (models/laguna.py through scoring/stream.py's ring
and scoring/server.py's session) against the plain reference's full
forward pass (benchmarks/models/laguna_stream.py), and each piece of the
block against a few lines of `jnp`.

Hidden 64, 2 key-value heads of 64 (a position's keys are one lane
tile), three layers of 4, 6 and 4 gated heads (full with the dense MLP,
sliding, full), sliding window 8, 16 experts of which 4 are held, 4 a
token, vocabulary 64: every kind of layer once, which is what a test
has to compile.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import gen, models
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.scoring.stream import (
    ContextAtRest,
    StreamingRing,
    pad_rows,
    streaming_step,
)

reference = models.load("laguna-stream")

W, P, S, D = 12, 40, 8, 6
ROPES = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
MC = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    num_experts=16, num_experts_per_tok=4, vocab_size=64, vocab_held=64,
    num_experts_held=4, first_expert=4, sliding_window=S, window=W,
    context_positions=P, rms_norm_eps=1e-6, moe_routed_scaling_factor=2.5,
    mlp_only_layers=[0], rope_parameters=ROPES,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"],
    gating_types=["per_head"] * 3,
    num_attention_heads_per_layer=[4, 6, 4])
ROUND_OFF = 1e-5          # ISSUE 32's: float32 round-off on scores of about 4


def program(**over):
    return build_model("laguna-stream", compute_dtype=jnp.float32,
                       **{**MC, **over})


def params_of(mc):
    return reference.tenant_params(11, 0, mc)


def readings(history, ticks, devices=D):
    fleet = gen.Fleet(11, 0, devices, 0.05, 12.0)
    hist = np.zeros((devices, history), np.float32)
    for k in range(history):
        hist[:, k] = fleet.values(k, spikes=False)
    frames = np.stack([fleet.values(history + k) for k in range(ticks)])
    return hist, frames


def store_with(hist, devices=64):
    """A host store that holds `hist` `[n, ticks]`, a minute a tick."""
    store = TelemetryStore(history=64, initial_devices=devices)
    n = hist.shape[0]
    for k in range(hist.shape[1]):
        store.append_measurements(MeasurementBatch(
            BatchContext(tenant_id="t"), np.arange(n, dtype=np.uint32),
            np.zeros(n, np.uint16), hist[:, k],
            np.full(n, k * 60.0, np.float64)))
    return store


def serve(model, params, hist, frames):
    """Seed from the stored windows (none where the fleet starts cold),
    then event by event. -> (scores [T, D], rows seeded again, ring)."""
    w = model.cfg.window
    ring = StreamingRing(model, capacity=D, initial_floor=D,
                         score_dtype="float32")
    ring.bind_params(params)
    if hist.shape[1]:
        ring.load(hist[:, -w:], np.full(D, w))
    out = [np.asarray(ring.update_and_score(
        model, params, np.arange(D, dtype=np.int32), v, 8))[:D]
        for v in frames]
    return np.stack(out), ring.reseeded, ring


# what seeding and streaming have to get right: (overrides, stored
# history, events, rows seeded again)
SEQUENCES = {
    # 12 stored values through a window of 8 (slots 4..7 then 0..3 hold
    # positions 4..11), then positions 12..37: the windows wrap at 16,
    # 24 and 32 and no context fills
    "a_history_longer_than_the_window_then_through_three_wraps": (
        {}, W + 4, 26, 0),
    # a window of 16: the sliding windows are seeded part full (12
    # positions), fill at position 16 and wrap from there
    "a_history_shorter_than_the_window": ({"sliding_window": 16}, W + 4, 26,
                                          0),
    # 24 positions: every full context fills after 12 events and again
    # after 12 more, and is seeded again from the last 12 values, the
    # sliding windows with it
    "a_context_that_fills_and_is_seeded_again": (
        {"context_positions": 24}, W + 4, 30, 2 * D),
    "a_cold_fleet": ({}, 0, 20, 0),
}


@pytest.mark.parametrize("case", SEQUENCES)
def test_seeding_then_streaming_agrees_with_the_full_forward_pass(case):
    """Prefill into wrapped slots, then decoding through the ring's two
    kinds of context, against the reference's full causal forward pass
    (banded on the sliding layers) over each device's whole sequence."""
    over, history, ticks, reseeds = SEQUENCES[case]
    mc = {**MC, **over}
    params = params_of(mc)
    hist, frames = readings(history, ticks)
    served, seeded_again, ring = serve(program(**over), params, hist, frames)
    ref = reference.run(params, hist, frames, np.ones(frames.shape, bool),
                        mc, "float32")
    assert seeded_again == reseeds
    if history:
        assert 3.0 < ref.mean() < 5.0 and (ref > 0).all()
    else:
        assert (ref[:8] == 0).all() and (ref[8:] > 0).all()
    assert np.abs(served - ref).max() < ROUND_OFF
    # a row's position is the full layers' alone: it passed the sliding
    # window long ago and nothing was seeded again for that
    assert int(np.asarray(ring.state["pos"])[:D].max()) > mc["sliding_window"]


def test_the_reference_in_blocks_and_through_a_tick_not_fed():
    params = params_of(MC)
    hist, frames = readings(W + 4, 10)
    fed = np.ones(frames.shape, bool)
    ref = reference.run(params, hist, frames, fed, MC, "float32")
    assert (reference.run(params, hist, frames, fed, MC, "float32",
                          block=5) == ref).all()
    fed[3, :3] = False
    skipped = reference.run(params, hist, frames, fed, MC, "float32")
    assert (skipped[:3] == ref[:3]).all() and (skipped[:, 3:] == ref[:, 3:]).all()
    assert (skipped[4:, :3] != ref[4:, :3]).any()


def test_seeding_leaves_the_last_positions_in_their_wrapped_slots():
    """`warm_state` on 12 stored values: a sliding layer's leaf holds
    positions 8..11 in slots 0..3 and 4..7 in slots 4..7, a full
    layer's positions 0..11 as they are; with 5 valid values of 12 a
    sliding leaf holds them in slots 0..4."""
    model, params = program(), params_of(MC)
    hist, _ = readings(W, 1)
    x, ok = jnp.asarray(hist), jnp.ones((D, W), bool)
    state = jax.jit(model.warm_state)(params, x, ok)
    tokens, count, _, _ = model._window_tokens(x, ok)
    _, entries = jax.jit(model._prefill)(params, tokens, count)
    for l, (k, v) in enumerate(entries):
        for name, entry in ((f"k{l}", k), (f"v{l}", v)):
            leaf = np.asarray(state[name])
            if name in model.wraps:
                assert leaf.shape[1] == S
                want = np.concatenate([entry[:, 8:12], entry[:, 4:8]], 1)
            else:
                assert leaf.shape[1] == P
                want = np.asarray(entry)
            assert (leaf[:, :want.shape[1]] == want).all(), name
    assert (np.asarray(state["pos"]) == W).all()
    few = jnp.arange(W)[None, :] >= W - 5
    state = jax.jit(model.warm_state)(params, x, jnp.broadcast_to(few, (D, W)))
    tokens, count, _, _ = model._window_tokens(x, jnp.broadcast_to(few, (D, W)))
    _, entries = jax.jit(model._prefill)(params, tokens, count)
    assert (np.asarray(state["pos"]) == 5).all()
    assert (np.asarray(state["k1"])[:, :5] == np.asarray(entries[1][0])[:, :5]).all()


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips of 4: each share's routed part, with the
    shared expert counted once, adds up to the reference's uncut layer."""
    uncut_mc = {**MC, "first_expert": 0, "num_experts_held": 16}
    full = reference.tenant_params(5, 0, uncut_mc)["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)
    want = reference.expert_layer(full, x, uncut_mc, "float32")
    shared = reference._mlp(full["shared"], x, "float32")
    total, live, seen = shared, jnp.ones(40, bool), 0
    for first in (0, 4, 8, 12):
        model = program(first_expert=first)
        held = {f"e{i}": full["experts"][f"e{first + i}"] for i in range(4)}
        idx, w = model.route(full["router"], x)
        part, counts = jax.jit(model.routed)(held, x, idx, w, live)
        total = total + part
        seen += int(counts.sum())
        # ...and a share alone is what the reference gives for that share
        alone = reference.expert_layer(
            {**full, "experts": held}, x, {**MC, "first_expert": first},
            "float32")
        assert np.abs(np.asarray(alone - shared - part)).max() < 1e-6
    assert seen == 40 * MC["num_experts_per_tok"]
    assert np.abs(np.asarray(total - want)).max() < 1e-6


def test_the_router_is_a_softmax_with_its_ten_best_kept():
    model = program()
    p = params_of(MC)["layer2"]["router"]
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    idx, w = model.route(p, x)
    probs = jax.nn.softmax(jnp.dot(x, p["w"].T, precision="highest"), -1)
    best = np.argsort(-np.asarray(probs), axis=1)[:, :4]
    assert (np.sort(np.asarray(idx), 1) == np.sort(best, 1)).all()
    kept = np.take_along_axis(np.asarray(probs), np.asarray(idx), 1)
    assert np.abs(np.asarray(w) - 2.5 * kept / kept.sum(1, keepdims=True)
                  ).max() < 1e-6
    dense = np.zeros((9, 16), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(w), 1)
    assert np.abs(dense - np.asarray(
        reference.routing_weights(p, x, MC))).max() < 1e-6


def _yarn_frequencies(rp, dim):
    """config.json's `rope_parameters` entry -> angular frequencies, from
    the published YaRN rule alone."""
    base = rp["rope_theta"]
    freq = base ** -(np.arange(0, dim, 2) / dim)
    if rp["rope_type"] != "yarn":
        return freq

    def dim_of(rotations):
        return dim * math.log(rp["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    return freq / rp["factor"] * ramp + freq * (1 - ramp)


@pytest.mark.parametrize("layer", [0, 1], ids=["full_yarn_on_half",
                                               "sliding_plain_on_all"])
def test_each_rope_against_a_few_lines(layer):
    """Queries and keys of `_project` at positions 0..19: the layer's
    frequencies on the rotated width (pairs (2i, 2i + 1)), cos and sin
    times the attention factor, the other dimensions untouched."""
    model, p = program(), params_of(MC)[f"layer{layer}"]
    u = jax.random.normal(jax.random.PRNGKey(7), (20, 64), jnp.float32)
    q, k, v = model._project(layer, p, u, jnp.arange(20))
    rp = ROPES[MC["layer_types"][layer]]
    dim = int(64 * rp["partial_rotary_factor"])
    angle = np.arange(20)[:, None] * _yarn_frequencies(rp, dim)[None, :]
    factor = rp.get("attention_factor", 1.0)
    cos, sin = np.cos(angle) * factor, np.sin(angle) * factor
    heads = MC["num_attention_heads_per_layer"][layer]
    for got, w, n in ((q, p["q"], heads), (k, p["k"], 2)):
        raw = np.asarray(jnp.dot(u, w.astype(jnp.float32),
                                 precision="highest")).reshape(20, n, 64)
        want = raw.copy()
        a, b = raw[..., 0:dim:2], raw[..., 1:dim:2]
        want[..., 0:dim:2] = a * cos[:, None] - b * sin[:, None]
        want[..., 1:dim:2] = a * sin[:, None] + b * cos[:, None]
        assert np.abs(np.asarray(got) - want).max() < 2e-5
        assert (dim == 64) or (np.asarray(got)[..., dim:]
                               == raw[..., dim:]).all()
    assert np.abs(np.asarray(v) - np.asarray(jnp.dot(
        u, p["v"].astype(jnp.float32), precision="highest"))).max() < 1e-5


@pytest.mark.parametrize("layer,heads", [(0, 4), (1, 6)],
                         ids=["full_4_heads", "sliding_6_heads"])
def test_decode_form_and_gate_against_a_few_lines(layer, heads):
    """One token through `_block_decode`'s attention half against plain
    per-head attention: head `h` reads key-value head `h // g`, a sliding
    layer the newest 8 positions wherever they rest, the gate is a
    sigmoid a head on the output before `Wo`."""
    model, p = program(), params_of(MC)[f"layer{layer}"]
    b, d, g = 5, 64, heads // 2
    sliding = MC["layer_types"][layer] == "sliding_attention"
    slots = S if sliding else P
    pos = jnp.asarray([0, 3, 7, 13, 22])
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 64), jnp.float32)
    kctx, vctx = (jax.random.normal(jax.random.PRNGKey(s), (b, slots, 2 * d),
                                    jnp.float32) for s in (2, 3))
    # the two window leaves as the ring hands them over: a table of the
    # five rows, each row's slot
    at = pos % slots if sliding else pos
    keys, vals = (ContextAtRest(table, jnp.arange(b), at)
                  for table in (kctx, vctx))
    got, k_new, v_new = model._attention(
        layer, p, x, pos, lambda q, k, v: model._attend_decode(
            layer, q, k, v, keys, vals, pos))
    for handle, table, entry in ((keys, kctx, k_new), (vals, vctx, v_new)):
        assert (np.asarray(handle.table) == np.asarray(
            table.at[jnp.arange(b), at].set(entry))).all()
    assert int(keys.read_rows) == 0         # the plain path gathers
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    q, k, v = model._project(layer, p, u, pos)
    assert (np.asarray(k_new) == np.asarray(k).reshape(b, -1)).all()
    gate = jax.nn.sigmoid(jnp.dot(u, p["head_gate"], precision="highest"))
    out = np.zeros((b, heads, d), np.float32)
    for i in range(b):
        t = int(pos[i])
        at = t % slots if sliding else t
        keys = np.asarray(kctx[i].at[at].set(k_new[i])).reshape(slots, 2, d)
        vals = np.asarray(vctx[i].at[at].set(v_new[i])).reshape(slots, 2, d)
        live = np.arange(slots) <= t            # wrapped: every slot
        for h in range(heads):
            logit = keys[live, h // g] @ np.asarray(q[i, h]) / math.sqrt(d)
            w = np.exp(logit - logit.max())
            out[i, h] = (w / w.sum()) @ vals[live, h // g]
    want = x + jnp.dot((out * np.asarray(gate)[..., None]).reshape(b, -1),
                       p["o"].astype(jnp.float32), precision="highest")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_window_leaves_are_appended_in_place_and_the_sliding_ones_wrap():
    """The jitted step's outputs alias its donated state leaf for leaf,
    and only the `(row, slot)` entries of a context differ afterwards:
    slot `pos` in a full layer's leaf, `pos mod 8` in a sliding one's."""
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
    for name in model.windows:
        changed = np.argwhere((np.asarray(state[name])
                               != before[name]).any(-1))
        slot = W % S if name in model.wraps else W
        assert {tuple(rc) for rc in changed} == {(5 + i, slot)
                                                 for i in range(D)}, name
    assert (np.asarray(state["pos"])[5:5 + D] == W + 1).all()
    text = compiled.as_text()
    for scope in ("ring_gather", "ctx_append", "ring_scatter", "gqa_project",
                  "attn_window", "attn_full", "head_gate", "moe_route",
                  "moe_experts", "dense_mlp", "lm_head"):
        assert scope in text, scope


def test_the_steps_new_numbers_reach_the_registry_through_a_session(run):
    """A session over the ring: scores against the reference, and the
    step's numbers on the registry: the bounded contexts' positions, the
    sliding windows' attended length (8 once wrapped), the rows whose
    append overwrote an older position, the rows seeded again."""
    mc = {**MC, "context_positions": 24}
    params = params_of(mc)
    hist, frames = readings(W + 4, 14)
    model = program(context_positions=24)

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
                            mc, "float32")
        assert np.abs(np.stack(served) - ref).max() < ROUND_OFF
        snap = dict(metrics._metrics)
        assert snap["scoring.ctx.positions"].count == 14
        assert snap["scoring.ctx.positions"]._max == 23
        # every step's sliding windows had wrapped already: 8 positions
        # attended to, every live row's append over an older position
        window = snap["scoring.ctx.window_positions"]
        assert window.count == 14 and window._max == S
        assert window.sum == 14 * S
        assert snap["scoring.ctx.wrapped"].value == 14 * D
        # positions 12..23, then seeded again from the last 12 values
        assert snap["scoring.ctx.reseeds"].value == D
        per_step = D * MC["num_experts_per_tok"] * 2
        assert snap["scoring.moe.assignments"].value == 14 * per_step
        assert 0 < snap["scoring.moe.assignments_held"].value < 14 * per_step
        assert snap["scoring.moe.runs_one_tile"].value == 14 * 2 * 4
        # the CPU's step is the plain path: no context read where it rests
        assert snap["scoring.ctx.at_rest_rows"].value == 0
        assert snap["scoring.ctx.read_positions"].value == 0
        s.close()

    run(main())


def test_a_model_without_wrapping_leaves_reports_no_window():
    """`layer_types` all full: no leaf wraps, the ring's bound is the
    contexts', and the wrapping leaves' two numbers stay 0 (as does
    the count of rows read at rest: the CPU's step gathers)."""
    over = dict(layer_types=["full_attention"] * 3)
    model = program(**over)
    assert not model.wraps and len(model.windows) == 6
    params = params_of({**MC, **over})
    hist, frames = readings(W, 2)
    ring = StreamingRing(model, capacity=D, initial_floor=D,
                         score_dtype="float32")
    assert ring._positions == P
    ring.bind_params(params)
    ring.load(hist, np.full(D, W))
    out = np.asarray(ring.update_and_score(
        model, params, np.arange(D, dtype=np.int32), frames[0], 8))
    assert model.step_stats[-4:] == ("ctx.window_positions", "ctx.wrapped",
                                     "ctx.at_rest", "ctx.read_positions")
    assert (out[-4:] == 0).all() and out[-6] == W


def test_configuration_the_model_cannot_compute_is_refused():
    with pytest.raises(ValueError, match="gating"):
        program(gating="none")
    with pytest.raises(ValueError, match="fewer than"):
        program(num_hidden_layers=4)
    with pytest.raises(ValueError, match="lane tiles"):
        program(head_dim=16)
    with pytest.raises(ValueError, match="past num_experts"):
        program(first_expert=14)


# -- what the ring and the shared blocks changed for the other models: nothing --

def _lowered_step(model, rows, bucket, out_dtype):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: model.init_state(rows))
    return jax.jit(streaming_step(model, out_dtype), donate_argnums=(1,)).lower(
        params, state, jax.ShapeDtypeStruct((bucket,), jnp.int32),
        jax.ShapeDtypeStruct((bucket,), jnp.float32)).as_text()


# sha256 of the lowered ring step (StableHLO text): `lstm-stream`'s at PR
# 31's tree, the same function, there, on the same arguments: it has
# held since. The four of the models with held experts were recorded
# anew at PR 39's tree, which made `SeqBlocks.routed`'s overflow ONE loop
# of grouped passes (tile `n` of every held expert at once) where it was
# one loop a held expert under a branch: 64 a layer in the model that PR
# brought, four fifths of its step's compile. The first tiles' lines are
# as they were; the loop behind them is what moved, and no frame of any
# cell enters it (`expert_one_tile_runs_per_step` reads every run). Until
# then `dsv3-stream`'s two held PR 31's text and `laguna-stream`'s PR
# 38's (its window leaves handed over at rest, `ContextAtRest`).
# `dsv3-stream`'s two were recorded anew when it came to hand its window
# leaves over at rest as well: the CPU's path gathers a layer's rows
# in its turn and appends its entries there, where the step gathered every
# layer's when it started and appended them when it ended, the same
# numbers to the bit (tests/test_dsv3.py, against the step as it was).
# `laguna-stream`'s two were recorded anew when the step came to return
# one number more, `ctx.read_positions` (on the CPU a zero a layer and
# their sum: constants, adds, a convert and a broadcast more, no other
# line). `olmo-hybrid-stream`'s two are tests/test_lfm2.py's
PARENTS_STEPS = {
    "dsv3-stream_float32": (
        "a4d3e50512730efbfabfa72ee17ab449984647a909fe1f887ded0cc3f7094b4e"),
    "dsv3-stream_bfloat16": (
        "897629aeafe643be59b80f21c5c0516661655c7db1bacedfb14f1644fbd16d36"),
    "lstm-stream": (
        "a2bd1f0a98b51e60dd3cc8f6c6d127a580d5712cec9d5c7608bf3d8c512680c8"),
    "laguna-stream_float32": (
        "2716b26e00e1e69971908d7e76e714aa7afa7c19de8e10e1900d530a949a7fdc"),
    "laguna-stream_bfloat16": (
        "39d7bc19a7615bcc3fe67025aa01e349d02d0c62271ef0cee0026a82546f59bd"),
}


@pytest.mark.parametrize("which", PARENTS_STEPS)
def test_the_other_models_steps_lower_to_the_parents_text(which):
    """`dsv3-stream` (tests/test_dsv3.py's size), `lstm-stream`
    (`stream-512k`'s widths) and `laguna-stream` (this file's size)
    lower to the text recorded for them above."""
    import hashlib

    from tests.test_dsv3 import MC as DSV3

    if which == "lstm-stream":
        text = _lowered_step(build_model("lstm-stream", window=64, hidden=64),
                             1025, 256, jnp.float16)
    elif which.startswith("laguna"):
        model = program() if which.endswith("32") else build_model(
            "laguna-stream", **MC)
        text = _lowered_step(model, 41, 16, jnp.float32)
    else:
        over = {"compute_dtype": jnp.float32} if which.endswith("32") else {}
        text = _lowered_step(build_model("dsv3-stream", **over, **DSV3), 41,
                             16, jnp.float32)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_STEPS[which]


@pytest.mark.parametrize("room,one_set", [(0.5, True), (1.5, False)])
def test_two_sets_of_weights_are_counted_beside_the_rings_table(
        monkeypatch, room, one_set):
    """What decides whether a session builds weights of its own: two
    sets of them AND the ring's table against the device's memory (3.4 GB
    of weights twice fit a v5e; beside 9.7 GB of contexts they do not)."""
    from sitewhere_tpu.scoring import server

    model = program()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    table = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(lambda: model.init_state(1025))))
    monkeypatch.setattr(server, "device_memory_bytes",
                        lambda: int(2 * weights + room * table))
    s = ScoringSession(model, TelemetryStore(history=64, initial_devices=D),
                       MetricsRegistry(),
                       ScoringConfig(buckets=(8,), capacity=D))
    assert s.ring.capacity == 1024 and 2 * weights < table
    assert s.one_set_only is one_set and (s.params is None) is one_set
    s.close()


def test_the_ring_is_asked_for_the_fleet_not_for_the_stores_capacity(
        monkeypatch):
    """A session asks its ring for the fleet-size hint or as far as the
    store holds values, and loads no further than the ring goes: a store
    of 64 rows behind a fleet of 10 does not make the table, which (its
    rows heavy beside this device's memory) is as long as the fleet."""
    from sitewhere_tpu.scoring import stream

    monkeypatch.setattr(stream, "device_memory_bytes", lambda: 1 << 20)
    empty = ScoringSession(program(), store_with(np.zeros((0, 0))),
                           MetricsRegistry(),
                           ScoringConfig(buckets=(8,), capacity=D))
    assert empty._fleet_rows() == D and empty.ring.capacity == D
    empty.close()
    store = store_with(readings(W, 1, devices=10)[0])
    s = ScoringSession(program(), store, MetricsRegistry(),
                       ScoringConfig(buckets=(8,), capacity=D),
                       params=params_of(MC))
    assert s._fleet_rows() == 10 and s.ring.capacity == 10
    s.warmup()                  # loads the fleet's 10 rows, not the store's 64
    assert s.ring.capacity == 10 < store.channels[0].capacity == 64
    pos = np.asarray(s.ring.state["pos"])
    assert (pos[:10] == W).all() and (pos[10:] == 0).all()
    s.close()
