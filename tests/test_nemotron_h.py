"""`nemotron-h-stream` at a small size on the CPU, float32 products,
seeded weights: the program (models/nemotron_h.py through
scoring/stream.py's ring and scoring/server.py's session) against the
plain reference's full forward pass
(benchmarks/models/nemotron_h_stream.py), compared on scores and on the
logits the last event leaves; Mamba-2's step form against the plain
scan; and the eight shares of an expert layer against the uncut layer.

Hidden 128; Mamba-2 layers of 4 heads of 64 (two to a row of 128
lanes) in 2 groups of state 64, conv 4; an attention layer of 4 query
heads on one key-value head of 128; expert layers of 64 routed experts
(6 a token) of which 8 are held, latent 128, experts of 128, a shared
expert of 256; 64 of 512 bins held; one period `MEM*E`: every kind of
layer, which is what a test has to compile.
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
from sitewhere_tpu.scoring.stream import StreamingRing

# the same six devices' readings and host store as the sibling's tests
from tests.test_laguna import readings, store_with  # noqa: E402

reference = models.load("nemotron-h-stream")

W, P, D = 12, 40, 6
MC = dict(
    hidden_size=128, expand=2, mamba_num_heads=4, mamba_head_dim=64,
    ssm_state_size=64, n_groups=2, conv_kernel=4, num_attention_heads=4,
    num_key_value_heads=1, head_dim=128, n_routed_experts=64,
    num_experts_per_tok=6, n_routed_experts_held=8, first_expert=0,
    moe_latent_size=128, moe_intermediate_size=128,
    moe_shared_expert_intermediate_size=256, routed_scaling_factor=5,
    vocab_size=512, vocab_held=64, num_hidden_layers=5,
    hybrid_override_pattern="MEM*E", num_nextn_predict_layers=0,
    layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, window=W, context_positions=P)
ROUND_OFF = 1e-5          # float32 round-off on scores of about 4


def program(**over):
    return build_model("nemotron-h-stream", compute_dtype=jnp.float32,
                       **{**MC, **over})


def params_of(mc, seed=11):
    return reference.tenant_params(seed, 0, mc)


def serve(model, params, hist, frames):
    """Seed from the stored windows (none where the fleet starts cold),
    then event by event. -> (scores [T, D], the ring)."""
    w = model.cfg.window
    ring = StreamingRing(model, capacity=D, initial_floor=D,
                         score_dtype="float32")
    ring.bind_params(params)
    if hist.shape[1]:
        ring.load(hist[:, -w:], np.full(D, w))
    out = [np.asarray(ring.update_and_score(
        model, params, np.arange(D, dtype=np.int32), v, 8))[:D]
        for v in frames]
    return np.stack(out), ring


def reference_logits(params, hist, frames, mc):
    """The logits the reference's full pass gives at each device's last
    event, over a sequence that never filled: the last `window` stored
    values, then every event."""
    ref = reference
    window, vocab = mc["window"], mc["vocab_held"]
    tokens, mean, var = ref._window_tokens(
        jnp.asarray(hist[:, -window:]), vocab=vocab)
    seq = [np.asarray(tokens)]
    n = jnp.full(D, window, jnp.int32)
    for v in frames:
        tok, _, (mean, var, n) = ref._event_tokens(
            mean, var, n, jnp.asarray(v), jnp.ones(D, bool), vocab=vocab,
            window=window)
        seq.append(np.asarray(tok)[:, None])
    fwd = ref._Forward(mc, "float32")
    x = fwd.hidden(params, jnp.asarray(np.concatenate(seq, 1)))
    return ref._ein("ni,io->no", ref._rms(x[:, -1], params["norm"],
                                          mc["layer_norm_epsilon"]),
                    params["head"], "float32")


# (overrides, stored history, events, rows seeded again)
SEQUENCES = {
    "a_seeded_window_then_events": ({}, W + 4, 20, 0),
    "a_cold_fleet": ({}, 0, 20, 0),
    # 24 positions: the attention layer's context fills after 12 events
    # and again after 12 more, and the row is seeded again from its last
    # 12 values, the matrix states and the conv's taps with it
    "a_context_that_fills_and_is_seeded_again": (
        {"context_positions": 24}, W + 4, 30, 2 * D),
    # another chip's share of each expert layer
    "the_fourth_share_of_the_experts": ({"first_expert": 24}, W + 4, 8, 0),
    # four heads of 32 to a row of lanes, one group of state
    "four_heads_to_a_row_of_lanes": (
        {"mamba_num_heads": 8, "mamba_head_dim": 32, "n_groups": 1},
        W + 4, 6, 0),
}


@pytest.mark.parametrize("case", SEQUENCES)
def test_seeding_then_streaming_agrees_with_the_full_forward_pass(case):
    """The prefill form's scan, then the decode form through the ring's
    rows in turn and its context, against the reference's full pass over
    each device's whole sequence (a plain loop over positions from `S =
    0`, a left-padded conv, one masked softmax, every held expert over
    every token): every served score, and where no row was seeded again
    the logits the last event leaves in `hn`."""
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
        assert 3.0 < ref.mean() < 6.0 and (ref > 0).all()
    else:
        assert (ref[:8] == 0).all() and (ref[8:] > 0).all()
    assert np.abs(served - ref).max() < ROUND_OFF
    if history and not reseeds:
        hn = np.asarray(ring.state["hn"][:D])
        logits = hn @ np.asarray(params["head"], np.float32)
        want = np.asarray(reference_logits(params, hist, frames, mc))
        assert np.abs(logits - want).max() < ROUND_OFF * np.abs(want).max()
    assert sorted(model.windows) == ["k3", "v3"]


def test_the_step_form_of_mamba2_is_the_plain_scan():
    """One Mamba-2 layer, the program's cell stepped one position at a
    time over a row's state as it rests (`_ssm_cell`, the decode form's
    own lines: `S` a head pair to a row of lanes, `B` and `C` laid over
    it) against the reference's plain loop over positions, a head's `[P,
    N]` state on its own: the layer's output at every position."""
    model = program()
    mc = {**MC}
    p = params_of(mc)["layer0"]
    n, s = 3, 9
    u = jax.random.normal(jax.random.PRNGKey(4), (n, s, 128), jnp.float32)
    want = reference._mamba(p, u, mc, "float32")
    z, xbc, dt, a = model._ssm_project(p, u)
    state = jnp.zeros((n,) + model._state_shape, jnp.float32)
    taps = jnp.zeros((n, 3 * model.cfg.conv_channels), jnp.float32)
    ys = []
    for t in range(s):
        y, state, taps, _ = model._ssm_cell(p, state, taps, xbc[:, t],
                                            dt[:, t], a[:, t])
        ys.append(y)
    got = model._ssm_out(p, jnp.zeros_like(u), jnp.stack(ys, 1), z)
    scale = float(jnp.abs(want).max())
    assert scale > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    # the prefill form is the same cell scanned
    prefill, _, _ = model._ssm_prefill(
        {**p, "norm": jnp.ones(128)}, u, jnp.full(n, s))
    normed = reference._rms(u, jnp.ones(128), mc["layer_norm_epsilon"])
    want = u + reference._mamba(p, normed, mc, "float32")
    assert float(jnp.abs(prefill - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


def test_eight_expert_shares_sum_to_the_uncut_layer():
    """An expert layer cut eight ways, as the configuration's deployment
    is (8 of 64 experts a chip): what each share gives, its latent sum
    sent back up, minus the shared expert that every chip computes
    alike, summed over the eight, plus the shared expert once, is what
    the reference gives for the whole layer."""
    whole = {**MC, "n_routed_experts_held": 64}
    params = params_of(whole)["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 128), jnp.float32)
    live = jnp.ones(16, bool)
    u = reference._rms(x, params["norm"], MC["layer_norm_epsilon"])
    shared = reference._relu2(params["shared"], u, "float32")
    total = shared
    for share in range(8):
        model = program(first_expert=8 * share)
        mine = {**params, "experts": {
            f"e{e}": params["experts"][f"e{8 * share + e}"]
            for e in range(8)}}
        y, counts = model._moe(mine, x, live)
        total = total + (y - x) - shared
        assert counts.shape == (8,)
    want = reference.expert_layer(params, u, whole, "float32")
    scale = float(jnp.abs(want).max())
    assert scale > 0.01
    # eight sums of 8 experts, each sent up on its own and the eight
    # added, against one sum of 64 sent up once: float32's order of sums
    assert float(jnp.abs(total - want).max()) < 1e-4 * scale
    # and the uncut layer in the program is the same
    y, counts = program(n_routed_experts_held=64)._moe(params, x, live)
    assert int(counts.sum()) == 16 * 6
    assert float(jnp.abs(y - x - want).max()) < 1e-5 * scale


def test_the_steps_numbers_reach_the_registry_through_a_session(run):
    """A session over the ring: scores against the reference, and on the
    registry the expert layers' counts, the context's positions, the
    mean decay, the largest magnitude a step left in a state; on the
    CPU's plain path no row is updated where it rests, so the state
    kernel's bytes read 0, and a feed of `n` rows counts `n` times a
    layer's row twice."""
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
        assert snap["scoring.moe.assignments"].value == 10 * D * 6 * 2
        held = snap["scoring.moe.assignments_held"].value
        assert 0 < held < 10 * D * 6 * 2
        assert snap["scoring.ctx.positions"].count == 10
        assert snap["scoring.ctx.positions"]._max == W + 9
        decay = snap["scoring.state.decay"]
        assert decay.count == 10 and 0.0 < decay.sum / 10 < 1.0
        assert snap["scoring.state.absmax"]._max > 0
        assert snap["scoring.state.in_place_rows"].value == 0
        assert snap["scoring.state.kernel_bytes"].value == 0
        assert snap["scoring.ctx.at_rest_rows"].value == 0
        assert snap["scoring.ctx.read_positions"].value == 0
        assert snap["scoring.ctx.reseeds"].value == 0
        s.close()

    run(main())
    metrics = MetricsRegistry()
    in_place = model.stat_feeds(metrics)[0][
        model.step_stats.index("state.in_place")]
    in_place(3.0)
    assert metrics.counter("scoring.state.in_place_rows").value == 3
    assert metrics.counter("scoring.state.kernel_bytes").value == \
        3 * 2 * (2 * 64 * 128 * 4)


def test_configuration_the_model_cannot_compute_is_refused():
    for over, what in (({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
                       ({"num_nextn_predict_layers": 1},
                        "num_nextn_predict_layers"),
                       ({"hybrid_override_pattern": "M-E*E"}, "kind"),
                       ({"n_routed_experts_held": 64, "first_expert": 8},
                        "past n_routed_experts"),
                       ({"n_groups": 4}, "groups of heads")):
        with pytest.raises(ValueError, match=what):
            program(**over)
