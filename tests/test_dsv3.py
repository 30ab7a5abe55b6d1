"""`dsv3-stream` at a small size on the CPU, float32 products, seeded
weights: the program (models/dsv3.py through scoring/stream.py's ring and
scoring/server.py's session) against the plain reference's full forward
pass (benchmarks/models/dsv3_stream.py), and the ring's window leaf.

Hidden 64, 4 heads, 16 experts of which 4 are held, 2 groups, vocabulary
64, 1 dense + 2 expert layers, one MTP module.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import gen, models
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring import server
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.scoring.stream import (
    ContextAtRest,
    StreamingRing,
    pad_rows,
    streaming_step,
)

reference = models.load("dsv3-stream")

W, P, D = 16, 24, 12
MC = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=16, n_group=2, topk_group=1,
    num_experts_per_tok=4, vocab_size=64, vocab_held=64,
    n_routed_experts_held=4, first_expert=4, mtp_modules=1, window=W,
    context_positions=P, rms_norm_eps=1e-6, rope_theta=10000,
    routed_scaling_factor=2.5,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1, "type": "yarn",
                  "original_max_position_embeddings": 4096})
ROUND_OFF = 5e-6          # float32 round-off on scores of about 4


def program(**over):
    return build_model("dsv3-stream", **{"compute_dtype": jnp.float32,
                                         **MC, **over})


@pytest.fixture(scope="module")
def params():
    return reference.tenant_params(11, 0, MC)


def readings(history=W + 4, ticks=30, devices=D):
    fleet = gen.Fleet(11, 0, devices, 0.05, 12.0)
    hist = np.zeros((devices, history), np.float32)
    for k in range(history):
        hist[:, k] = fleet.values(k, spikes=False)
    frames = np.stack([fleet.values(history + k) for k in range(ticks)])
    return hist, frames


def serve(ring, model, params, hist, frames):
    """Seed from the stored windows, then event by event (a row whose
    context filled is seeded again by the ring before its next event).
    -> (scores [T, D], rows seeded again)."""
    ring.bind_params(params)
    ring.load(hist[:, -W:], np.full(D, W))
    out = [np.asarray(ring.update_and_score(
        model, params, np.arange(D, dtype=np.int32), v, 16))[:D]
        for v in frames]
    return np.stack(out), ring.reseeded


def test_seeding_then_streaming_agrees_with_the_full_forward_pass(params):
    """Prefill, then decoding through the ring's context, against the
    reference's full causal forward over each device's whole sequence; 30
    events through 24 positions, so every context fills and is seeded
    again from its stored window three times over."""
    hist, frames = readings()
    model = program()
    ring = StreamingRing(model, capacity=D, initial_floor=D,
                         score_dtype="float32")
    served, reseeds = serve(ring, model, params, hist, frames)
    ref = reference.run(params, hist, frames, np.ones(frames.shape, bool),
                        MC, "float32")
    assert reseeds == 3 * D
    assert 3.0 < ref.mean() < 5.0 and (ref > 0).all()
    assert np.abs(served - ref).max() < ROUND_OFF
    # the reference in blocks of sequences: the same scores bit for bit
    assert (reference.run(params, hist, frames, np.ones(frames.shape, bool),
                          MC, "float32", block=5) == ref).all()
    # a device keeps its sequence through a tick it was not fed
    fed = np.ones(frames.shape, bool)
    fed[3, :6] = False
    skipped = reference.run(params, hist, frames, fed, MC, "float32")
    assert (skipped[:3] == ref[:3]).all() and (skipped[:, 6:] == ref[:, 6:]).all()
    assert (skipped[4:, :6] != ref[4:, :6]).any()


def test_a_cold_fleet_is_gated_then_agrees(params):
    hist, frames = readings(history=0, ticks=12)
    model = program()
    ring = StreamingRing(model, capacity=D, initial_floor=D,
                         score_dtype="float32")
    ring.bind_params(params)
    served = np.stack([np.asarray(ring.update_and_score(
        model, params, np.arange(D, dtype=np.int32), v, 16))[:D]
        for v in frames])
    ref = reference.run(params, hist, frames, np.ones(frames.shape, bool),
                        MC, "float32")
    assert (ref[:8] == 0).all() and (ref[8:] > 0).all()
    assert np.abs(served - ref).max() < ROUND_OFF


def test_decode_form_agrees_with_prefill_form(params):
    """One more token through `step_score` (W_kvb folded into the query
    and the output, latents only) against the prefill form over the
    sequence one longer (keys and values rebuilt per head)."""
    hist, frames = readings(ticks=1)
    model = program()
    ok = jnp.ones((D, W), bool)
    state = jax.jit(model.warm_state)(params, jnp.asarray(hist[:, -W:]), ok)

    def step(params, state, v):
        # the window leaves as the ring hands them over: the table, the
        # rows, each row's slot
        rows = {name: ContextAtRest(leaf, jnp.arange(D), state["pos"])
                if name in model.at_rest else leaf
                for name, leaf in state.items()}
        _, out, _ = model.step_score(params, rows, v, jnp.ones(D, bool))
        return out, {name: rows[name].table for name in model.at_rest}

    rows, tables = jax.jit(step)(params, state, jnp.asarray(frames[0]))
    # the same W + 1 tokens, all through the prefill form
    tokens, count, _, _ = model._window_tokens(jnp.asarray(hist[:, -W:]), ok)
    mean, var = state["mean"], state["var"]
    new = model._bin((frames[0] - mean) / jnp.sqrt(var + 1e-6))
    longer = jnp.concatenate([tokens, new[:, None]], 1)
    h, entries = jax.jit(model._prefill)(params, longer, count + 1)
    for l, entry in enumerate(entries):
        assert np.abs(np.asarray(tables[f"ctx{l}"][:, W]
                                 - entry[:, W])).max() < 2e-6
        assert np.abs(np.asarray(state[f"ctx{l}"][:, :W]
                                 - entry[:, :W])).max() < 2e-6
    from sitewhere_tpu.models.dsv3 import _rms

    hn = _rms(h[:, W], params["norm"], 1e-6)
    assert np.abs(np.asarray(rows["hn"] - hn)).max() < 2e-5


def test_the_shares_add_up_to_the_uncut_layer(params):
    """16 experts over 4 chips of 4: each share's routed part, with the
    shared expert counted once, adds up to the reference's uncut layer."""
    uncut_mc = {**MC, "first_expert": 0, "n_routed_experts_held": 16}
    full = reference.tenant_params(5, 0, uncut_mc)["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)
    want = reference.expert_layer(full, x, uncut_mc, "float32")
    total = reference._mlp(full["shared"], x, "float32")
    live = jnp.ones(40, bool)
    seen = 0
    for first in (0, 4, 8, 12):
        model = program(first_expert=first)
        held = {f"e{i}": full["experts"][f"e{first + i}"] for i in range(4)}
        idx, w = model.route(full["router"], x)
        part, counts = jax.jit(model.routed)(held, x, idx, w, live)
        total = total + part
        seen += int(counts.sum())
        # ...and a share alone is what the reference gives for that share
        share_mc = {**MC, "first_expert": first}
        alone = reference.expert_layer({**full, "experts": held}, x, share_mc,
                                       "float32")
        assert np.abs(np.asarray(
            alone - reference._mlp(full["shared"], x, "float32")
            - part)).max() < 1e-6
    assert seen == 40 * MC["num_experts_per_tok"]
    assert np.abs(np.asarray(total - want)).max() < 1e-6
    # rows that are padding compute and count nothing
    part, counts = jax.jit(program().routed)(
        {f"e{i}": full["experts"][f"e{4 + i}"] for i in range(4)}, x,
        *program().route(full["router"], x), live.at[:20].set(False))
    assert (np.asarray(part[:20]) == 0).all() and int(counts.sum()) < seen


# the cases `routed`'s grouped pass tells apart: the model's share,
# tokens, which rows are live, the tile as a function of the busiest
# run's length, and the held experts' runs that do NOT fit one tile
ROUTED_CASES = {
    "an_expert_with_no_token": (dict(first_expert=0), 2, None, None, 0),
    "a_run_of_exactly_one_tile": (dict(first_expert=0), 40, None,
                                  lambda busiest: busiest, 0),
    "a_run_longer_than_one_tile": (dict(first_expert=0), 40, None,
                                   lambda busiest: busiest - 1, 1),
    "every_pair_on_a_held_expert": (
        dict(first_expert=0, n_routed_experts_held=16), 40, None,
        lambda busiest: busiest // 3, None),
    "rows_not_live": (dict(first_expert=0), 40, 20, None, 0),
    "first_expert_above_zero": (dict(first_expert=8), 40, None,
                                lambda busiest: busiest // 2, None),
}


@pytest.mark.parametrize("case", ROUTED_CASES)
def test_the_grouped_pass_agrees_with_the_reference(case):
    """The held experts' part (program) against `reference.expert_layer`
    less the shared expert, float32, 1e-6; each held expert's count; and
    the runs the straight-line pass serves whole (`moe.runs_one_tile`)."""
    from sitewhere_tpu.models.dsv3 import EXPERT_TILE, runs_one_tile

    over, tokens, dead, tile_of, overflowing = ROUTED_CASES[case]
    mc = {**MC, **over}
    first, held = mc["first_expert"], mc["n_routed_experts_held"]
    full = reference.tenant_params(5, 0, {
        **MC, "first_expert": 0, "n_routed_experts_held": 16})["layer1"]
    share = {f"e{i}": full["experts"][f"e{first + i}"] for i in range(held)}
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, 64), jnp.float32)
    live = jnp.ones(tokens, bool).at[:dead or 0].set(False)
    model = program(**over)
    idx, w = model.route(full["router"], x)
    local = np.asarray(idx)[np.asarray(live)].reshape(-1) - first
    want_counts = np.bincount(local[(local >= 0) & (local < held)],
                              minlength=held)
    tile = tile_of(want_counts.max()) if tile_of else EXPERT_TILE
    part, counts = jax.jit(model.routed, static_argnames="tile")(
        share, x, idx, w, live, tile=tile)
    assert (np.asarray(counts) == want_counts).all()
    want = reference.expert_layer({**full, "experts": share}, x, mc,
                                  "float32") \
        - reference._mlp(full["shared"], x, "float32")
    keep = np.asarray(live)
    assert np.abs(np.asarray(want - part))[keep].max() < 1e-6
    assert (np.asarray(part)[~keep] == 0).all()
    # what the case is there for
    if case == "an_expert_with_no_token":
        assert want_counts.min() == 0
    if case == "every_pair_on_a_held_expert":
        assert want_counts.sum() == tokens * mc["num_experts_per_tok"]
    if overflowing is None:
        overflowing = (want_counts > tile).sum()
        assert 0 < overflowing
    else:
        assert (want_counts > tile).sum() == overflowing
    assert int(runs_one_tile(counts, tile)) == held - overflowing


def test_mtp_forecast_agrees_with_the_reference(params):
    hist, _ = readings()
    model = program()
    x, ok = jnp.asarray(hist[:, -W:]), jnp.ones((D, W), bool)
    draft, after = jax.jit(model.forecast_bins)(params, x, ok)
    ref_draft, ref_after = reference.forecast_bins(params, hist[:, -W:], MC,
                                                   "float32")
    assert (np.asarray(draft) == ref_draft).all()
    assert np.abs(np.asarray(after) - ref_after).max() < ROUND_OFF
    out = np.asarray(model.forecast(params, x, ok))
    assert out.shape == (D, 2, 1) and np.isfinite(out).all()
    # the module is its own weights: without it, the main head again
    bare = program(mtp_modules=0)
    _, own = jax.jit(bare.forecast_bins)(params, x, ok)
    assert np.abs(np.asarray(own) - ref_after).max() > 1e-3


def test_window_leaf_is_appended_in_place(params):
    """The jitted step's outputs alias its donated state leaf for leaf,
    and only the `(row, position)` entries of a context differ
    afterwards. (Whether the buffer is also written in place is the
    backend's: the CPU copies it first; tests/test_dsv3_tpu_compile.py
    reads the TPU's compiled step, chip_smoke.py the chip's.)"""
    hist, frames = readings(ticks=1)
    model = program()
    step = jax.jit(streaming_step(model), donate_argnums=(1,))
    cap = 40
    state = jax.device_put(model.init_state(cap + 1))
    seeded = jax.jit(model.warm_state)(params, jnp.asarray(hist[:, -W:]),
                                       jnp.ones((D, W), bool))
    state = jax.tree.map(lambda leaf, rows: leaf.at[5:5 + D].set(rows),
                         state, seeded)
    before = jax.tree.map(np.asarray, state)
    dev = np.concatenate([np.arange(5, 5 + D, dtype=np.int32),
                          pad_rows(cap, 16 - D)])   # padding: dropped
    v = np.zeros(16, np.float32)
    v[:D] = frames[0]
    compiled = step.lower(params, state, dev, v).compile()
    state, scores = compiled(params, state, dev, v)
    assert scores.shape == (16 + len(model.step_stats),)
    import re

    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", 
                        compiled.as_text()).group(1)
    assert aliases.count("may-alias") + aliases.count("must-alias") \
        == len(state)
    for l in range(model.layers):
        changed = np.argwhere((np.asarray(state[f"ctx{l}"])
                               != before[f"ctx{l}"]).any(-1))
        assert {tuple(rc) for rc in changed} == {(5 + i, W)
                                                 for i in range(D)}
    assert (np.asarray(state["pos"])[5:5 + D] == W + 1).all()
    # the padding read the scratch row and wrote it no more than any
    # other row the step was not given
    for name, leaf in state.items():
        if name not in model.windows:
            kept = np.ones(cap + 1, bool)
            kept[5:5 + D] = False
            assert (np.asarray(leaf)[kept] == before[name][kept]).all(), name
    text = compiled.as_text()
    for scope in ("ring_gather", "ctx_append", "ring_scatter", "mla_project",
                  "mla_attend", "moe_route", "moe_experts", "dense_mlp",
                  "lm_head"):
        assert scope in text, scope


def _parents_step(model):
    """The ring step as it was before the window leaves were handed over
    where they rest, written out from the model's pieces: every leaf's
    rows gathered when the step starts, each layer's attention
    `_attend_decode` over its gathered context, every layer's entry
    appended when the step ends, the other leaves scattered back."""
    from sitewhere_tpu.models.dsv3 import _rms
    from sitewhere_tpu.scoring.stream import DISTINCT_ROWS, _rows

    c = model.cfg

    def step(params, state, dev, v):
        live = dev < state["pos"].shape[0] - 1
        rows = {name: _rows(leaf, dev) for name, leaf in state.items()}
        pos = rows["pos"]
        token, score, out = model._arrive(params, rows, v)
        x = params["embed"][token].astype(jnp.float32)
        at = jnp.minimum(pos, c.context_positions - 1)
        cos, sin = jnp.asarray(model._cos)[at], jnp.asarray(model._sin)[at]
        for l in range(model.layers):
            p = params[f"layer{l}"]
            q_nope, q_rope, out[f"ctx{l}"] = model._project(
                p, _rms(x, p["attn_norm"], c.rms_norm_eps), cos, sin)
            x = x + model._mm(model._attend_decode(
                p, q_nope, q_rope, out[f"ctx{l}"], rows[f"ctx{l}"], pos),
                p["o"])
            y, _ = model._ffn(p, _rms(x, p["mlp_norm"], c.rms_norm_eps), live)
            x = x + y
        out["hn"] = _rms(x, params["norm"], c.rms_norm_eps).astype(
            c.compute_dtype)
        return {name: leaf.at[(dev, pos) if name in model.windows else dev]
                .set(out[name], **DISTINCT_ROWS)
                for name, leaf in state.items()}, score

    return step


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_plain_path_is_the_parents_step_bit_for_bit(params, dtype):
    """The window leaves handed over where they rest, on the CPU's path
    (the rows gathered in the layer's turn, `_attend_decode`, the append):
    the parent's scores and next state to the bit, in float32 and in
    bfloat16 products, with padding in the frame; `ctx.at_rest` 0."""
    hist, frames = readings(ticks=3)
    model = program(compute_dtype=getattr(jnp, dtype))
    cap = 20
    seeded = jax.jit(model.warm_state)(params, jnp.asarray(hist[:, -W:]),
                                       jnp.ones((D, W), bool))
    state = jax.tree.map(lambda leaf, rows: leaf.at[3:3 + D].set(rows),
                         model.init_state(cap + 1), seeded)
    dev = np.concatenate([np.arange(3, 3 + D, dtype=np.int32),
                          pad_rows(cap, 16 - D)])
    step, parents = jax.jit(streaming_step(model)), jax.jit(
        _parents_step(model))
    want_state = got_state = state
    for frame in frames:
        v = np.zeros(16, np.float32)
        v[:D] = frame
        got_state, got = step(params, got_state, dev, v)
        want_state, want = parents(params, want_state, dev, v)
        assert (np.asarray(got[:16]) == np.asarray(want)).all()
        assert model.step_stats[-1] == "ctx.at_rest" and float(got[-1]) == 0
        for name, leaf in want_state.items():
            assert (np.asarray(got_state[name]) == np.asarray(leaf)).all(), \
                name
    assert np.abs(np.asarray(want[:D])).max() > 1.0


def test_lstm_stream_lowers_to_the_same_program_as_before():
    """The ring's contract for a model without window leaves written out
    (gather whole rows that ascend, step, write whole distinct rows
    back) against the code `lstm-stream` goes through beside
    `dsv3-stream`: the same StableHLO, so the window leaves cost its
    compiled step nothing."""
    model = build_model("lstm-stream", window=64, hidden=64)

    def old_step(params, state, dev, v):
        with jax.named_scope("ring_gather"):
            rows = jax.tree.map(
                lambda leaf: leaf.at[dev].get(
                    mode="clip", indices_are_sorted=True), state)
        with jax.named_scope("cell_step"):
            scores, new_rows = model.step_score(params, rows, v)
        with jax.named_scope("ring_scatter"):
            state = jax.tree.map(
                lambda leaf, rows_new: leaf.at[dev].set(
                    rows_new, mode="drop", unique_indices=True),
                state, new_rows)
        return state, scores.astype(jnp.float16)

    params = model.init(jax.random.PRNGKey(0))
    state = model.init_state(1025)
    dev, v = jnp.zeros(256, jnp.int32), jnp.zeros(256, jnp.float32)

    def text(fn):
        return jax.jit(fn, donate_argnums=(1,)).lower(
            params, state, dev, v).as_text().replace(fn.__name__, "step")

    assert text(streaming_step(model, jnp.float16)) == text(old_step)


def _fill(store, hist):
    for k in range(hist.shape[1]):
        store.append_measurements(MeasurementBatch(
            BatchContext(tenant_id="t"), np.arange(D, dtype=np.uint32),
            np.zeros(D, np.uint16), hist[:, k],
            np.full(D, k * 60.0, np.float64)))


def test_session_holds_no_weights_until_bound_and_never_two_sets(
        params, monkeypatch, run):
    """Weights of which the device cannot hold two sets: the session
    builds none, is ready to be handed the first set, warms when it gets
    it, serves (scores against the reference, the step's numbers on the
    registry, a full context seeded again), frees a set before placing
    its successor, and holds nothing once closed."""
    hist, frames = readings(ticks=10)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    monkeypatch.setattr(server, "device_memory_bytes",
                        lambda: int(1.5 * weights))
    made = []
    model = program()
    monkeypatch.setattr(type(model), "init", lambda self, rng: made.append(
        1) or reference.tenant_params(0, 0, MC))

    async def main():
        store = TelemetryStore(history=64, initial_devices=D)
        _fill(store, hist)
        metrics = MetricsRegistry()
        s = ScoringSession(model, store, metrics, ScoringConfig(
            buckets=(16,), threshold=4.5, score_dtype="float32", capacity=D))
        assert s.one_set_only and s.params is None
        await s.warmup_async()
        assert s.ready and not s.flush_due
        assert not [m for m in made if m == 1][1:]    # eval_shape at most
        s.swap_params(params)
        assert not s.ready
        while not s.ready:
            await __import__("asyncio").sleep(0.01)
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
        snap = {n: m for n, m in metrics._metrics.items()}
        per_step = D * MC["num_experts_per_tok"] * 2
        assert snap["scoring.moe.assignments"].value == 10 * per_step
        assert 0 < snap["scoring.moe.assignments_held"].value < 10 * per_step
        assert snap["scoring.moe.expert_max_tokens"].count == 10
        # 12 tokens a step: every held expert's run is one tile or less
        assert snap["scoring.moe.runs_one_tile"].value == 10 * 2 * 4
        assert snap["scoring.ctx.positions"].count == 10
        assert snap["scoring.ctx.positions"]._max == P - 1
        assert snap["scoring.ctx.reseeds"].value == D     # after event 8
        # a successor: the old set is freed first, the state seeded again
        old = jax.tree.leaves(s.params)
        s.swap_params(reference.tenant_params(12, 0, MC))
        assert all(leaf.is_deleted() for leaf in old)
        assert s.version == 2 and s.ready
        s.close()
        assert s.params is None and s.ring.state is None

    run(main())


def test_weights_that_fit_twice_are_built_as_before(monkeypatch):
    """The LSTM's session, and this model's where memory allows: a set of
    its own from the start, nothing deleted at a swap."""
    monkeypatch.setattr(server, "device_memory_bytes", lambda: 1 << 40)
    store = TelemetryStore(history=64, initial_devices=D)
    s = ScoringSession(program(), store, MetricsRegistry(),
                       ScoringConfig(buckets=(16,), capacity=D))
    assert not s.one_set_only and s.params is not None
    old = jax.tree.leaves(s.params)
    s.swap_params(s.model.init(jax.random.PRNGKey(1)))
    assert not any(leaf.is_deleted() for leaf in old)
    s.close()
