"""Streaming scorer tests: the event-native hot path (models/lstm.py
StreamingLstmModel + scoring/stream.py StreamingRing) that replaces the
per-event window rescan — ONE cell step per event on resident state.
This is the benchmark's default model; its behavior is pinned here:
detection parity with the windowed scorer, state regrow, fault
recovery, and the checkpoint-rollout reseed."""

import asyncio

import numpy as np
import pytest

from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.pool import PoolConfig, SharedScoringPool
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.scoring.stream import StackedStreamingRing, StreamingRing
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

from tests.test_pipeline import wait_until
from tests.test_scoring import _fill_store


def _session(store, buckets=(256,), threshold=4.0, window=64):
    s = ScoringSession(
        build_model("lstm-stream", window=window), store, MetricsRegistry(),
        ScoringConfig(buckets=buckets, threshold=threshold))
    s.warmup()
    return s


def test_streaming_detects_injected_anomalies(run):
    """Same detection bar the windowed scorer passes: 12-sigma spikes
    separate cleanly through the one-step-per-event hot path."""

    async def main():
        store = TelemetryStore(history=128, initial_devices=200)
        sim = DeviceSimulator(SimConfig(num_devices=200, seed=3), tenant_id="t")
        _fill_store(store, sim, 70)
        s = _session(store)
        assert isinstance(s.ring, StreamingRing)
        sim.cfg = SimConfig(num_devices=200, seed=3, anomaly_rate=0.05,
                            anomaly_magnitude=12.0)
        hits, truths = [], []
        for k in range(5):
            batch, truth = sim.tick(t=(70 + k) * 60.0)
            store.append_measurements(batch)
            s.admit(batch)
            scored = await s.flush()
            hits.append(scored.is_anomaly)
            truths.append(truth)
        det, tr = np.concatenate(hits), np.concatenate(truths)
        assert (det == tr).mean() > 0.97
        assert det[tr].mean() > 0.9
        s.close()

    run(main())


def test_streaming_matches_windowed_on_warm_history(run):
    """First post-warmup flush: streaming scores (state seeded by window
    replay) agree with the windowed model's scores to within the
    documented normalization drift — same weights, same events."""

    async def main():
        store = TelemetryStore(history=128, initial_devices=100)
        sim = DeviceSimulator(SimConfig(num_devices=100, seed=7), tenant_id="t")
        _fill_store(store, sim, 70)
        stream = _session(store, threshold=4.0)
        windowed = ScoringSession(
            build_model("lstm", window=64), store, MetricsRegistry(),
            ScoringConfig(buckets=(256,), threshold=4.0))
        windowed.warmup()
        # same params: streaming shares the windowed param format
        windowed.params = stream.params
        batch, _ = sim.tick(t=70 * 60.0)
        store.append_measurements(batch)
        for s in (stream, windowed):
            s.admit(batch)
        a = await stream.flush()
        b = await windowed.flush()
        # warm_state replays the very window the windowed model scans, so
        # the standing predictions coincide; normalization frames differ
        # by one step of Welford drift
        np.testing.assert_allclose(a.score, b.score, atol=0.15)
        stream.close()
        windowed.close()

    run(main())


def test_streaming_regrow_preserves_state(run):
    """A device index past capacity triggers regrow; old devices' state
    survives and new devices score once they accrue history."""

    async def main():
        store = TelemetryStore(history=64, initial_devices=100)
        sim = DeviceSimulator(SimConfig(num_devices=100, seed=1), tenant_id="t")
        _fill_store(store, sim, 40)
        s = _session(store, buckets=(128,), window=32)
        cap0 = s.ring.capacity
        s.ring.ensure_capacity(cap0 + 10)
        assert s.ring.capacity > cap0
        # old rows kept their history count; fresh rows start cold
        counts = np.asarray(s.ring.state["count"])
        assert counts[:100].min() >= 8
        assert counts[cap0:cap0 + 5].max() == 0
        # still scores after the regrow
        batch, _ = sim.tick(t=41 * 60.0)
        s.admit(batch)
        scored = await s.flush()
        assert scored.score.shape[0] == 100
        s.close()

    run(main())


def test_streaming_fault_recovery_reloads_from_host(run):
    """A faulted ring (donated state lost) recovers by replaying host
    windows — same story as the window ring."""

    async def main():
        store = TelemetryStore(history=64, initial_devices=50)
        sim = DeviceSimulator(SimConfig(num_devices=50, seed=2), tenant_id="t")
        _fill_store(store, sim, 40)
        s = _session(store, buckets=(64,), window=32)
        s.ring.faulted = True
        s._recover_ring()
        assert not s.ring.faulted
        assert np.asarray(s.ring.state["count"])[:50].min() >= 8
        batch, _ = sim.tick(t=41 * 60.0)
        s.admit(batch)
        scored = await s.flush()
        assert scored.score.shape[0] == 50
        s.close()

    run(main())


def test_streaming_swap_params_reseeds_state(run):
    """Code-review regression: a checkpoint rollout must reseed the
    resident streaming state under the NEW weights — stale h/c/pred from
    the old weights mis-scores until it washes out."""

    async def main():
        import jax

        store = TelemetryStore(history=128, initial_devices=50)
        sim = DeviceSimulator(SimConfig(num_devices=50, seed=5), tenant_id="t")
        _fill_store(store, sim, 70)
        s = _session(store)
        old_pred = np.asarray(s.ring.state["pred"][:50]).copy()
        new_params = s.model.init(jax.random.PRNGKey(99))
        s.swap_params(new_params)
        # reference: a session born with the new weights (identical
        # seeding path) — the swapped session must match it, not the
        # stale old-weight state
        fresh = ScoringSession(
            build_model("lstm-stream", window=64), store, MetricsRegistry(),
            ScoringConfig(buckets=(256,)), params=new_params)
        fresh.warmup()
        np.testing.assert_allclose(np.asarray(s.ring.state["pred"][:50]),
                                   np.asarray(fresh.ring.state["pred"][:50]),
                                   atol=1e-5)
        # and it genuinely changed (old state would have been wrong)
        assert np.abs(np.asarray(s.ring.state["pred"][:50])
                      - old_pred).max() > 1e-3
        assert s.version == 1
        s.close()
        fresh.close()

    run(main())


# -- the state's layout in the table: one leaf `hc` for h and c --------------

WIDTHS = [(16, 1), (64, 1), (64, 2)]     # (hidden, layers)
W, D, B, TICKS = 16, 40, 24, 24          # window, devices, bucket, ticks


def _plain_seed(model, params, x):
    """`warm_state` by hand on full windows: h and c of each layer as
    arrays of their own."""
    import jax.numpy as jnp

    from sitewhere_tpu.models.common import lstm_scan

    cdt = model.cfg.compute_dtype
    mean = x.mean(-1)
    var = ((x - mean[:, None]) ** 2).mean(-1)
    seq = ((x - mean[:, None]) / jnp.sqrt(var + 1e-6)[:, None])[:, :, None]
    hs, cs = [], []
    for layer in range(model.cfg.layers):
        seq, (h, c) = lstm_scan(params[f"lstm{layer}"], seq, cdt)
        seq = seq.astype(cdt)
        hs.append(h)
        cs.append(c)
    pred = (seq[:, -1].astype(jnp.float32) @ params["head"]["w"]
            + params["head"]["b"])[:, 0]
    count = jnp.full(x.shape[0], min(x.shape[1], model.cfg.window), jnp.int32)
    return pred, mean, jnp.maximum(var, 1e-6), count, hs, cs


def _plain_tick(model, params, st, dev, v):
    """One event a row of `dev`, the equations of `step_score` on state
    that keeps every layer's h and c apart; returns (state, scores)."""
    import jax.numpy as jnp

    cfg = model.cfg
    pred, mean, var, count, hs, cs = st
    m, s2, n = mean[dev], var[dev], count[dev]
    score = jnp.clip(
        jnp.where(n >= max(8, cfg.window // 8),
                  jnp.abs((v - m) / jnp.sqrt(s2 + 1e-6) - pred[dev]), 0.0),
        0.0, cfg.score_clip)
    n1 = jnp.minimum(n + 1, cfg.window)
    m1 = m + (v - m) / n1
    s21 = s2 + ((v - m1) * (v - m) - s2) / n1
    x = ((v - m1) / jnp.sqrt(s21 + 1e-6))[:, None]
    hs, cs = list(hs), list(cs)
    for layer in range(cfg.layers):
        h, c = model._cell(params, layer, x, hs[layer][dev], cs[layer][dev])
        hs[layer] = hs[layer].at[dev].set(h)
        cs[layer] = cs[layer].at[dev].set(c)
        x = h
    p1 = (x @ params["head"]["w"] + params["head"]["b"])[:, 0]
    return (pred.at[dev].set(p1), mean.at[dev].set(m1), var.at[dev].set(s21),
            count.at[dev].set(n1), hs, cs), score


@pytest.mark.parametrize("start", ["init_state", "warm_state"])
@pytest.mark.parametrize("vmapped", [False, True],
                         ids=["dedicated", "vmapped"])
@pytest.mark.parametrize("hidden,layers", WIDTHS)
def test_step_over_hc_matches_a_loop_that_keeps_h_and_c_apart(
        hidden, layers, vmapped, start):
    """24 ticks through the ring's jitted step, cold and seeded, dedicated
    and under `vmap`, against a plain loop with h and c of each layer in
    arrays of their own: packing them into `hc` moves no value."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.scoring.stream import streaming_step

    model = build_model("lstm-stream", window=W, hidden=hidden,
                        layers=layers)
    rng = np.random.default_rng(hidden + layers)
    tenants = 2 if vmapped else 1
    step = streaming_step(model)
    step = jax.jit(jax.vmap(step) if vmapped else step)
    plain = jax.jit(lambda params, st, dev, v:
                    _plain_tick(model, params, st, dev, v))
    hist = rng.normal(20.0, 3.0, (tenants, D + 1, W)).astype(np.float32)
    states, plains, params = [], [], []
    for t in range(tenants):
        p = model.init(jax.random.PRNGKey(7 + t))
        params.append(p)
        if start == "warm_state":
            states.append(jax.jit(model.warm_state)(
                p, jnp.asarray(hist[t]), jnp.ones((D + 1, W), bool)))
            plains.append(_plain_seed(model, p, jnp.asarray(hist[t])))
        else:
            states.append(model.init_state(D + 1))
            zeros = [jnp.zeros((D + 1, hidden), jnp.float32)] * layers
            plains.append((jnp.zeros(D + 1), jnp.zeros(D + 1),
                           jnp.ones(D + 1), jnp.zeros(D + 1, jnp.int32),
                           zeros, zeros))
    if vmapped:
        state = jax.tree.map(lambda *leaves: jnp.stack(leaves), *states)
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *params)
    else:
        state, stacked = states[0], params[0]
    for _ in range(TICKS):
        # unique ids a tenant, the tail of the bucket on the scratch row
        dev = np.full((tenants, B), D, np.int32)
        for t in range(tenants):
            dev[t, :B - 3] = rng.permutation(D)[:B - 3]
        v = rng.normal(20.0, 3.0, (tenants, B)).astype(np.float32)
        if vmapped:
            state, got = step(stacked, state, dev, v)
        else:
            state, got = step(stacked, state, dev[0], v[0])
            got = got[None]
        for t in range(tenants):
            plains[t], want = plain(params[t], plains[t], dev[t], v[t])
            np.testing.assert_allclose(np.asarray(got[t, :B - 3]),
                                       np.asarray(want[:B - 3]),
                                       rtol=1e-6, atol=1e-6)
    assert float(np.asarray(got).max()) > 0.0       # the gate opened


@pytest.mark.parametrize("hidden,layers", WIDTHS)
def test_state_tree_is_one_shape_and_rests_in_whole_tiles(hidden, layers):
    """`init_state` and `warm_state` declare the same tree, and every
    leaf of two or more dimensions a minor dimension of whole 128-lane
    tiles (scoring/stream.py, "Contract with the model")."""
    import jax
    import jax.numpy as jnp

    model = build_model("lstm-stream", window=W, hidden=hidden,
                        layers=layers)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cold = jax.eval_shape(lambda: model.init_state(D))
    warm = jax.eval_shape(model.warm_state, params,
                          jax.ShapeDtypeStruct((D, W), jnp.float32),
                          jax.ShapeDtypeStruct((D, W), jnp.bool_))
    assert jax.tree.structure(cold) == jax.tree.structure(warm)
    assert jax.tree.leaves(cold) == jax.tree.leaves(warm)
    assert cold["hc"].shape == (D, -(-2 * hidden * layers // 128) * 128)
    for leaf in jax.tree.leaves(cold):
        assert leaf.shape[0] == D
        assert leaf.ndim == 1 or leaf.shape[-1] % 128 == 0, leaf


@pytest.mark.parametrize("hidden,layers", WIDTHS)
def test_lowered_step_scatters_once_a_state_leaf(hidden, layers):
    """One scatter a leaf, five in all whatever the depth: a leaf added
    later shows up here, in review."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.scoring.stream import streaming_step

    model = build_model("lstm-stream", window=W, hidden=hidden,
                        layers=layers)
    state = model.init_state(D + 1)
    text = jax.jit(streaming_step(model)).lower(
        model.init(jax.random.PRNGKey(0)), state,
        jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)).as_text()
    assert text.count('"stablehlo.scatter"(') == len(state) == 5


# -- pooled streaming (config 4 at streaming speed) -------------------------


def _make_pool_tenant(pool, tid, n_devices, seed, delivered, params=None,
                      threshold=4.0, ticks=70):
    store = TelemetryStore(history=128, initial_devices=n_devices)
    sim = DeviceSimulator(SimConfig(num_devices=n_devices, seed=seed),
                          tenant_id=tid)
    _fill_store(store, sim, ticks)
    delivered[tid] = []

    async def deliver(scored, tid=tid):
        delivered[tid].append(scored)

    slot = pool.register(tid, store, threshold, deliver, params=params)
    return store, sim, slot


def test_pool_streaming_uses_stacked_streaming_ring(run):
    """A streaming model in the shared pool gets the streaming stacked
    ring (one cell step per event), not the windowed W-step rescan."""

    async def main():
        model = build_model("lstm-stream", window=64)
        pool = SharedScoringPool(model, MetricsRegistry(),
                                 PoolConfig(batch_buckets=(64,),
                                            batch_window_ms=1.0))
        delivered: dict[str, list] = {}
        _make_pool_tenant(pool, "a", 20, 3, delivered)
        assert isinstance(pool.ring, StackedStreamingRing)
        await wait_until(lambda: pool.ready, timeout=60.0)
        assert np.asarray(pool.ring.state["count"])[0, :20].min() >= 8
        pool.close()

    run(main())


def test_pool_streaming_matches_dedicated_sessions(run):
    """Parity: N tenants scored through the shared streaming pool get
    the SAME scores as each tenant alone in a dedicated streaming
    session — same weights, same events, same seeding path."""

    async def main():
        import jax

        model = build_model("lstm-stream", window=64)
        params = {tid: model.init(jax.random.PRNGKey(i + 10))
                  for i, tid in enumerate(("a", "b"))}
        pool = SharedScoringPool(model, MetricsRegistry(),
                                 PoolConfig(batch_buckets=(64,),
                                            batch_window_ms=1.0))
        delivered: dict[str, list] = {}
        stores, sims = {}, {}
        for i, tid in enumerate(("a", "b")):
            stores[tid], sims[tid], _ = _make_pool_tenant(
                pool, tid, 30, i + 20, delivered, params=params[tid])
        await wait_until(lambda: pool.ready, timeout=60.0)

        # dedicated reference sessions share the host stores (already
        # seeded) and the exact params
        refs = {}
        for tid in ("a", "b"):
            refs[tid] = ScoringSession(
                build_model("lstm-stream", window=64), stores[tid],
                MetricsRegistry(), ScoringConfig(buckets=(64,)),
                params=params[tid])
            refs[tid].warmup()

        for k in range(3):
            expect = {}
            for tid in ("a", "b"):
                batch, _ = sims[tid].tick(t=(70 + k) * 60.0)
                stores[tid].append_measurements(batch)
                pool.admit(tid, batch)
                refs[tid].admit(batch)
                expect[tid] = await refs[tid].flush()
            await wait_until(
                lambda k=k: all(len(delivered[t]) == k + 1
                                for t in ("a", "b")), timeout=30.0)
            for tid in ("a", "b"):
                got = delivered[tid][k]
                order = np.argsort(got.device_index)
                ref_order = np.argsort(expect[tid].device_index)
                # pooled (vmap over the stack) vs dedicated flushes round
                # to fp16 independently at readback (score_dtype default):
                # one fp16 ulp at z≈8 is ~0.008, so parity holds to ~2e-2
                np.testing.assert_allclose(
                    got.score[order], expect[tid].score[ref_order],
                    atol=2e-2)
        for r in refs.values():
            r.close()
        pool.close()

    run(main())


def test_pool_streaming_swap_params_reseeds_slot(run):
    """Checkpoint rollout on ONE pooled tenant reseeds only that
    tenant's streaming state under the new weights; neighbors keep
    their state untouched."""

    async def main():
        import jax

        model = build_model("lstm-stream", window=64)
        pool = SharedScoringPool(model, MetricsRegistry(),
                                 PoolConfig(batch_buckets=(64,),
                                            batch_window_ms=1.0))
        delivered: dict[str, list] = {}
        stores, slots = {}, {}
        for i, tid in enumerate(("a", "b")):
            stores[tid], _, slots[tid] = _make_pool_tenant(
                pool, tid, 25, i + 30, delivered)
        await wait_until(lambda: pool.ready, timeout=60.0)
        slot_a = pool.stack.slots["a"]
        slot_b = pool.stack.slots["b"]
        pred_a0 = np.asarray(pool.ring.state["pred"][slot_a, :25]).copy()
        pred_b0 = np.asarray(pool.ring.state["pred"][slot_b, :25]).copy()

        new_params = model.init(jax.random.PRNGKey(99))
        version = slots["a"].swap_params(new_params)
        assert version == 1
        # a's state moved to the new weights...
        pred_a1 = np.asarray(pool.ring.state["pred"][slot_a, :25])
        assert np.abs(pred_a1 - pred_a0).max() > 1e-3
        # ...and matches a dedicated session born with them
        ref = ScoringSession(
            build_model("lstm-stream", window=64), stores["a"],
            MetricsRegistry(), ScoringConfig(buckets=(64,)),
            params=new_params)
        ref.warmup()
        np.testing.assert_allclose(
            pred_a1, np.asarray(ref.ring.state["pred"][:25]), atol=1e-5)
        # b untouched
        np.testing.assert_allclose(
            np.asarray(pool.ring.state["pred"][slot_b, :25]), pred_b0,
            atol=0.0)
        ref.close()
        pool.close()

    run(main())
