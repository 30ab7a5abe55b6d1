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


def _scalar(ring, name):
    """One of the per-row scalars the model keeps in its row leaf, for
    every row of `ring` (dedicated `[rows]`, stacked `[tenants, rows]`)."""
    return np.asarray(ring.model.scalars(ring.state["row"])[name])


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
        counts = _scalar(s.ring, "count")
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
        assert _scalar(s.ring, "count")[:50].min() >= 8
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
        old_pred = _scalar(s.ring, "pred")[:50].copy()
        new_params = s.model.init(jax.random.PRNGKey(99))
        s.swap_params(new_params)
        # reference: a session born with the new weights (identical
        # seeding path) — the swapped session must match it, not the
        # stale old-weight state
        fresh = ScoringSession(
            build_model("lstm-stream", window=64), store, MetricsRegistry(),
            ScoringConfig(buckets=(256,)), params=new_params)
        fresh.warmup()
        np.testing.assert_allclose(_scalar(s.ring, "pred")[:50],
                                   _scalar(fresh.ring, "pred")[:50],
                                   atol=1e-5)
        # and it genuinely changed (old state would have been wrong)
        assert np.abs(_scalar(s.ring, "pred")[:50] - old_pred).max() > 1e-3
        assert s.version == 1
        s.close()
        fresh.close()

    run(main())


# -- the state's layout in the table: one row leaf for h, c and the scalars ---

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
    and under `vmap`, against a plain loop with h and c of each layer and
    every scalar in arrays of their own: packing them into one row leaf
    moves no value."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.scoring.stream import pad_rows, streaming_step

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
        # ascending ids a tenant, the tail of the bucket padding: past the
        # table for the step (dropped), on the scratch row for the loop
        dev = np.tile(pad_rows(D, B), (tenants, 1))
        for t in range(tenants):
            dev[t, :B - 3] = np.sort(rng.permutation(D)[:B - 3])
        v = rng.normal(20.0, 3.0, (tenants, B)).astype(np.float32)
        if vmapped:
            state, got = step(stacked, state, dev, v)
        else:
            state, got = step(stacked, state, dev[0], v[0])
            got = got[None]
        for t in range(tenants):
            plains[t], want = plain(params[t], plains[t],
                                    np.minimum(dev[t], D), v[t])
            np.testing.assert_allclose(np.asarray(got[t, :B - 3]),
                                       np.asarray(want[:B - 3]),
                                       rtol=1e-6, atol=1e-6)
    assert float(np.asarray(got).max()) > 0.0       # the gate opened
    # and the table holds what the loop holds, row for row (the scratch
    # row apart: the loop's padding wrote it, the step's wrote nothing)
    for t in range(tenants):
        row = state["row"][t] if vmapped else state["row"]
        flat = np.asarray(row).reshape(D + 1, -1)
        pred, mean, var, count, hs, cs = plains[t]
        for name, want in zip(("pred", "mean", "var", "count"),
                              (pred, mean, var, count)):
            np.testing.assert_allclose(
                np.asarray(model.scalars(row)[name])[:D],
                np.asarray(want, np.float32)[:D], rtol=1e-6, atol=1e-6)
        for layer in range(layers):
            at = 2 * layer * hidden
            for k, want in enumerate((hs[layer], cs[layer])):
                np.testing.assert_allclose(
                    flat[:D, at + k * hidden:at + (k + 1) * hidden],
                    np.asarray(want)[:D], rtol=1e-6, atol=1e-6)


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
    # one leaf: h and c of every layer, then the four scalars, in whole
    # 128-lane tiles; a one-dimensional leaf would cost a gather and a
    # scatter of its own over the whole fleet
    assert list(cold) == ["row"]
    assert cold["row"].shape == (
        D, -(-(2 * hidden * layers + 4) // 128), 128)
    assert cold["row"].dtype == jnp.float32
    fresh = model.scalars(model.init_state(3)["row"])
    assert {k: float(v[0]) for k, v in fresh.items()} == {
        "pred": 0.0, "mean": 0.0, "var": 1.0, "count": 0.0}


@pytest.mark.parametrize("tiles", [1, 2, 3])
@pytest.mark.parametrize("live", [0, 5, 1024, 1500, 2048])
def test_distinct_rows_scatter_writes_the_named_rows_only(tiles, live):
    """What the ring's scatters say (`DISTINCT_ROWS`) over what its
    engines hand them (ascending rows, then `pad_rows`): the named rows
    written, every other row and the scratch row bit for bit, alone and
    under `vmap` (the pool), for no live row, a few, a whole bucket and
    between, rows of one to three tiles."""
    import jax
    import jax.numpy as jnp
    from sitewhere_tpu.scoring.stream import DISTINCT_ROWS, pad_rows

    rows_n, bucket = 3001, 2048
    rng = np.random.default_rng(tiles * 7 + live)
    table = rng.normal(size=(rows_n, tiles, 128)).astype(np.float32)
    rows = rng.normal(size=(bucket, tiles, 128)).astype(np.float32)
    dev = np.concatenate([
        np.sort(rng.permutation(rows_n - 1)[:live]).astype(np.int32),
        pad_rows(rows_n - 1, bucket - live)])
    want = table.copy()
    want[dev[:live]] = rows[:live]

    def put(table, dev, rows):
        return table.at[dev].set(rows, **DISTINCT_ROWS)

    assert (np.asarray(put(jnp.asarray(table), dev, rows)) == want).all()
    stacked = jax.vmap(put)(jnp.stack([table, table + 1]),
                            jnp.stack([dev, dev]), jnp.stack([rows, rows]))
    assert (np.asarray(stacked[0]) == want).all()
    want1 = table + 1
    want1[dev[:live]] = rows[:live]
    assert (np.asarray(stacked[1]) == want1).all()


def _table_ops(text: str, op: str, rows: int) -> list[str]:
    """The attributes of each `stablehlo.<op>` of a lowered step whose
    first operand is a table of `rows` rows."""
    import re

    return [attrs for attrs, operand in re.findall(
        rf'"stablehlo\.{op}"\([^\n]*? <\{{(.*?)\}}>(?: \(\{{.*?\}}\))? : '
        r'\(tensor<(\d+)x',
        text, re.S) if int(operand) == rows]


@pytest.mark.parametrize("hidden,layers", WIDTHS)
def test_lowered_step_scatters_once_a_state_leaf(hidden, layers):
    """One gather and one scatter, of the one leaf, whatever the depth (a
    leaf added later shows up here, in review). The scatter is told that
    no row comes twice and NOT that they ascend, the gather that they
    ascend: what a v5e measured as free, and as a sixth slower (PERF.md
    section 6, PR 31)."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.scoring.stream import streaming_step

    model = build_model("lstm-stream", window=W, hidden=hidden,
                        layers=layers)
    state = model.init_state(D + 1)
    text = jax.jit(streaming_step(model)).lower(
        model.init(jax.random.PRNGKey(0)), state,
        jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)).as_text()
    scatters, gathers = (_table_ops(text, op, D + 1)
                         for op in ("scatter", "gather"))
    assert len(scatters) == len(gathers) == len(state) == 1
    assert text.count('"stablehlo.scatter"(') == 1
    assert all("indices_are_sorted = false" in line
               and "unique_indices = true" in line for line in scatters)
    assert all("indices_are_sorted = true" in line for line in gathers)


def test_lowered_dsv3_step_scatters_once_a_state_leaf():
    """`dsv3-stream`'s step: one scatter a leaf, the context appends
    among them, every one told that no row comes twice and none that
    they ascend (a context append so told took thirty times as long on
    a v5e); one sorted gather a leaf (the model's own lookups and
    scatters, of an embedding or over experts, are not the table's)."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.scoring.stream import streaming_step
    from tests.test_dsv3 import program

    model = program()
    state = model.init_state(D + 1)
    assert set(model.windows) < set(state)
    text = jax.jit(streaming_step(model)).lower(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), state,
        jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)).as_text()
    scatters = _table_ops(text, "scatter", D + 1)
    assert len(scatters) == len(state)
    assert all("indices_are_sorted = false" in line
               and "unique_indices = true" in line for line in scatters)
    gathers = _table_ops(text, "gather", D + 1)
    assert len(gathers) == len(state)
    assert all("indices_are_sorted = true" in line for line in gathers)


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
        assert _scalar(pool.ring, "count")[0, :20].min() >= 8
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
        pred_a0 = _scalar(pool.ring, "pred")[slot_a, :25].copy()
        pred_b0 = _scalar(pool.ring, "pred")[slot_b, :25].copy()

        new_params = model.init(jax.random.PRNGKey(99))
        version = slots["a"].swap_params(new_params)
        assert version == 1
        # a's state moved to the new weights...
        pred_a1 = _scalar(pool.ring, "pred")[slot_a, :25]
        assert np.abs(pred_a1 - pred_a0).max() > 1e-3
        # ...and matches a dedicated session born with them
        ref = ScoringSession(
            build_model("lstm-stream", window=64), stores["a"],
            MetricsRegistry(), ScoringConfig(buckets=(64,)),
            params=new_params)
        ref.warmup()
        np.testing.assert_allclose(
            pred_a1, _scalar(ref.ring, "pred")[:25], atol=1e-5)
        # b untouched
        np.testing.assert_allclose(
            _scalar(pool.ring, "pred")[slot_b, :25], pred_b0, atol=0.0)
        ref.close()
        pool.close()

    run(main())


# -- the ring's contract with the engines: the rows of a step ascend --------
#
# The step tells the compiler that its rows ascend and that none comes
# twice, so a take that arrives shuffled or names a device twice must be
# put right by the engine BEFORE the ring sees it: handed on as it came it
# would, on a backend that uses the promise, silently corrupt the table.

FLEET, BUCKET = 48, 32


def _take(kind: str) -> np.ndarray:
    """Device ids of one take, in arrival order."""
    rng = np.random.default_rng(len(kind))
    if kind == "ascending":             # fills its bucket: no padding
        return np.sort(rng.permutation(FLEET)[:BUCKET])
    if kind == "short":                 # ascending, most of the bucket padding
        return np.sort(rng.permutation(FLEET)[:5])
    if kind == "shuffled":              # no id twice, in no order
        ids = rng.permutation(FLEET)[:BUCKET - 4]
        assert (ids[1:] < ids[:-1]).any()
        return ids
    if kind == "repeats":               # ids up to four times, in no order
        return rng.integers(0, 9, BUCKET - 4)
    assert kind == "sorted-repeats"     # in order, but not strictly
    return np.array([3, 3, 3, 7, 9, 9, 20])


def _per_event_loop(model, params, table, dev, v):
    """One event after another on a host copy of the table, each through
    `step_score` alone: (the table afterwards, the scores in order)."""
    import jax

    one = jax.jit(model.step_score)
    table, scores = table.copy(), []
    for d, x in zip(dev, v):
        s, new = one(params, {"row": table[d][None]}, np.float32([x]))
        table[d] = np.asarray(new["row"])[0]
        scores.append(float(s[0]))
    return table, np.array(scores, np.float32)


def _batch(dev, v, t):
    from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch

    n = dev.shape[0]
    return MeasurementBatch(BatchContext(tenant_id="a"), dev.astype(np.uint32),
                            np.zeros(n, np.uint16), v,
                            np.full(n, t, np.float64))


@pytest.mark.parametrize("engine", ["session", "pool"])
@pytest.mark.parametrize(
    "kind", ["ascending", "short", "shuffled", "repeats", "sorted-repeats"])
def test_a_take_in_any_order_scores_as_a_per_event_loop(run, engine, kind):
    """Scores in arrival order and the final table of a plain per-event
    loop, whatever order the take arrived in; every row the take did not
    name, the scratch row and a neighbouring tenant's rows among them,
    bit for bit as it was; `scoring.ring.ascending` counts the takes that
    needed no host sort."""

    async def main():
        model = build_model("lstm-stream", window=64)
        metrics = MetricsRegistry()
        dev = _take(kind)
        v = np.random.default_rng(5).normal(20.0, 3.0, dev.shape[0]).astype(
            np.float32)
        store = TelemetryStore(history=128, initial_devices=FLEET)
        sim = DeviceSimulator(SimConfig(num_devices=FLEET, seed=3),
                              tenant_id="a")
        _fill_store(store, sim, 70)
        if engine == "session":
            s = ScoringSession(model, store, metrics, ScoringConfig(
                buckets=(BUCKET,), score_dtype="float32"))
            s.warmup()
            params, ring, rows = s.params, s.ring, slice(None)
        else:
            pool = SharedScoringPool(model, metrics, PoolConfig(
                batch_buckets=(BUCKET,), batch_window_ms=1.0,
                score_dtype="float32"))
            delivered: dict[str, list] = {}
            for tid, seed in (("a", 3), ("b", 4)):
                other = TelemetryStore(history=128, initial_devices=FLEET)
                _fill_store(other, DeviceSimulator(
                    SimConfig(num_devices=FLEET, seed=seed), tenant_id=tid), 70)

                async def deliver(scored, tid=tid):
                    delivered[tid].append(scored)

                delivered[tid] = []
                pool.register(tid, other if tid == "b" else store, 4.0,
                              deliver)
            await wait_until(lambda: pool.ready, timeout=60.0)
            params, ring = pool.stack.get_params("a"), pool.ring
            rows = pool.stack.slots["a"]
        before = np.asarray(ring.state["row"])
        # what the ring is handed, whatever this backend makes of the
        # promise: every row of ids strictly ascending, padding included
        step, handed = ring.update_and_score, []

        def watched(model, params, ids, *rest, **kw):
            handed.append(np.atleast_2d(ids))
            return step(model, params, ids, *rest, **kw)

        ring.update_and_score = watched
        counts = {n: metrics.counter(n).value
                  for n in ("scoring.ring.ascending", "scoring.dispatches")}
        if engine == "session":
            s.admit(_batch(dev, v, 71 * 60.0))
            scored = await s.flush()
        else:
            pool.admit("a", _batch(dev, v, 71 * 60.0))
            await wait_until(lambda: delivered["a"], timeout=30.0)
            scored = delivered["a"][0]
            assert not delivered["b"]
        after = np.asarray(ring.state["row"])
        assert handed and all((np.diff(ids, axis=1) > 0).all()
                              for ids in handed)
        want, scores = _per_event_loop(model, params, before[rows], dev, v)
        assert (scored.device_index == dev).all()
        assert scores.max() > 0.0                 # seeded: the gate is open
        np.testing.assert_allclose(scored.score, scores, rtol=1e-5, atol=1e-5)
        named = np.zeros(before[rows].shape[0], bool)
        named[dev] = True
        np.testing.assert_allclose(after[rows][named], want[named],
                                   rtol=1e-5, atol=1e-6)
        untouched = np.ones(before.shape[:-2], bool)
        untouched[rows] = ~named                  # a view: the scratch row
        assert untouched[rows][-1]                # stays among them
        assert (after[untouched] == before[untouched]).all()
        rounds = int(np.unique(dev, return_counts=True)[1].max())
        took = {n: metrics.counter(n).value - c for n, c in counts.items()}
        assert took == {
            "scoring.ring.ascending": float(kind in ("ascending", "short")),
            "scoring.dispatches": float(rounds)}
        (s if engine == "session" else pool).close()

    run(main())


# -- window leaves bounded one by one (a wrapping leaf beside a bounded one) --


class _TwoBounds:
    """The least model with two kinds of window in one row: `near` holds
    4 positions and wraps, `far` holds 8 and is bounded; an entry is the
    event's value in every lane, the score the value."""

    streaming = True
    windows = {"near": "pos", "far": "pos"}
    step_stats = ()
    seed_rows = 2

    class cfg:
        window = 5          # longer than `near`

    def __init__(self, wraps=("near",), near=4, far=8):
        self.wraps, self.bounds = frozenset(wraps), {"near": near, "far": far}

    def init(self, rng):
        import jax.numpy as jnp

        return {"one": jnp.ones(())}

    def init_state(self, cap):
        import jax.numpy as jnp

        return {"pos": jnp.zeros(cap, jnp.int32),
                **{name: jnp.zeros((cap, n, 128), jnp.float32)
                   for name, n in self.bounds.items()}}

    def step_score(self, params, rows, v, live):
        import jax.numpy as jnp

        entry = jnp.broadcast_to(v[:, None], (v.shape[0], 128))
        return v * params["one"], {"pos": rows["pos"] + 1, "near": entry,
                                   "far": entry}, None

    def warm_state(self, params, x, valid):
        """The stored values as positions 0..count-1, where the steps
        would have left them."""
        import jax.numpy as jnp

        n, w = x.shape
        count = valid.sum(1)
        first = (jnp.arange(w)[None, :] + (w - count)[:, None]) % w
        vals = jnp.take_along_axis(x, first, axis=1)
        state = self.init_state(n)
        state["pos"] = count.astype(jnp.int32)
        for name, bound in self.bounds.items():
            for p in range(w):
                slot = p % bound if name in self.wraps else p
                state[name] = state[name].at[:, slot].set(jnp.where(
                    (p < count)[:, None], vals[:, p, None],
                    state[name][:, slot]))
        return state


def _two_bounds_ring(model, events):
    """Three rows seeded from values 1..5, then `events` events of
    values 6, 7, ...: (ring, the values each row holds by leaf)."""
    import jax

    ring = StreamingRing(model, capacity=3, initial_floor=3)
    ring.bind_params(model.init(jax.random.PRNGKey(0)))
    ring.load(np.tile(np.arange(1, 6, dtype=np.float32), (3, 1)),
              np.full(3, 5))
    for k in range(events):
        out = np.asarray(ring.update_and_score(
            model, ring._params, np.arange(3, dtype=np.int32),
            np.full(3, 6.0 + k, np.float32), 4))
        assert (out[:3] == 6.0 + k).all()
    held = {name: np.asarray(ring.state[name])[:3, :, 0]
            for name in model.windows}
    return ring, held


@pytest.mark.parametrize("events", [0, 1, 2, 3])
def test_a_wrapping_leaf_takes_position_p_at_p_mod_its_bound(events):
    """Value `p + 1` is position `p`: `near` keeps the newest 4 in
    their wrapped slots, seeded (5 values through 4 slots) and stepped
    alike; `far` keeps every position where it is; and the bound that
    says "full" is `far`'s alone."""
    ring, held = _two_bounds_ring(_TwoBounds(), events)
    assert ring._positions == 8 and ring.reseeded == 0
    last = 5 + events                     # positions 0..last-1 are filled
    want_near = [max(p for p in range(last) if p % 4 == s) + 1
                 for s in range(4)]
    assert (held["near"] == want_near).all()
    assert (held["far"][:, :last] == np.arange(1, last + 1)).all()
    assert (held["far"][:, last:] == 0).all()
    assert (np.asarray(ring.state["pos"])[:3] == last).all()
    assert (ring._filled[:3] == last).all()


def test_a_row_is_full_by_its_bounded_leaf_alone_and_then_seeded_again():
    """Positions 5, 6, 7 fill `far`; the fourth event finds the rows
    full, seeds them again from their last 5 values (4..8: `near`
    wraps them as the seeding does) and lands at position 5."""
    ring, held = _two_bounds_ring(_TwoBounds(), 4)
    assert ring.reseeded == 3
    assert (np.asarray(ring.state["pos"])[:3] == 6).all()
    assert (held["far"][:, :6] == [4, 5, 6, 7, 8, 9]).all()
    assert (held["near"] == [8, 9, 6, 7]).all()


def test_a_row_whose_leaves_all_wrap_is_never_full():
    ring, held = _two_bounds_ring(_TwoBounds(wraps=("near", "far")), 12)
    assert ring._positions == 0 and ring.reseeded == 0
    assert (np.asarray(ring.state["pos"])[:3] == 17).all()
    assert (held["near"] == [17, 14, 15, 16]).all()
    assert (held["far"] == [17, 10, 11, 12, 13, 14, 15, 16]).all()


def test_without_wraps_the_smallest_bound_is_the_rows():
    """No leaf wraps (what `dsv3-stream` declares): the smallest bound
    among the leaves says "full", as the one bound did."""
    ring, held = _two_bounds_ring(_TwoBounds(wraps=(), near=6), 1)
    assert ring._positions == 6 and ring.reseeded == 0
    assert (held["near"] == [1, 2, 3, 4, 5, 6]).all()
    ring.update_and_score(ring.model, ring._params,
                          np.arange(3, dtype=np.int32),
                          np.full(3, 7.0, np.float32), 4)
    assert ring.reseeded == 3
    assert (np.asarray(ring.state["near"])[:3, :, 0] == [2, 3, 4, 5, 6, 7]).all()


@pytest.mark.parametrize("slice_bytes,blocks", [(1 << 19, 1), (3072, 2),
                                                (2048, 3), (1024, 6)])
def test_heavy_rows_are_gathered_in_blocks_of_positions(monkeypatch,
                                                        slice_bytes, blocks):
    """A window leaf's rows for reading: whole where a row weighs no more
    than one gathered slice may (how every leaf was read before), else in
    the fewest equal blocks of positions that do, through a view of the
    table; the same rows either way, the scratch row for padding."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.scoring import stream

    monkeypatch.setattr(stream, "GATHER_SLICE_BYTES", slice_bytes)
    table = jax.random.normal(jax.random.PRNGKey(0), (9, 6, 256), jnp.float32)
    dev = jnp.asarray([1, 4, 5, 9, 10], jnp.int32)     # two of padding
    text = jax.jit(lambda t, d: stream._rows(t, d)).lower(
        table, dev).as_text()
    assert (f"tensor<9x{blocks}x{6 // blocks}x256xf32>" in text) \
        == (blocks > 1)
    assert text.count('"stablehlo.gather"(') == 1
    got = np.asarray(stream._rows(table, dev))
    assert (got == np.asarray(table)[[1, 4, 5, 8, 8]]).all()
    # a leaf of one value a row, or of one tile a row, is read as it was
    flat = jnp.arange(9.0)
    assert (np.asarray(stream._rows(flat, dev)) == [1, 4, 5, 8, 8]).all()


@pytest.mark.parametrize("limit,asked,rows", [
    (None, 768, 1024),                 # a backend that reports no memory
    (1 << 40, 768, 1024),              # light rows: the next power of two
    (12 << 20, 768, 768),              # 1,024 rows would take over half
    (12 << 20, 512, 512),              # ...under the floor of 1,024 too
    (1 << 20, 1024, 1024),             # a power of two is what was asked
], ids=["no_limit", "light_rows", "heavy_rows", "heavy_rows_under_floor",
        "a_power_of_two"])
def test_a_table_is_rounded_up_only_while_that_costs_little(
        monkeypatch, limit, asked, rows):
    """`table_rows`: rows of 6,148 B (`_TwoBounds`); 1,024 of them are
    6.3 MB, over half of a device of 12 MiB."""
    from sitewhere_tpu.scoring import stream

    monkeypatch.setattr(stream, "device_memory_bytes", lambda: limit)
    got = stream.table_rows(_TwoBounds(), asked, 1024)
    assert got == rows
    ring = StreamingRing(_TwoBounds(), capacity=asked)
    assert ring.capacity == got
    assert ring.state["far"].shape == (got + 1, 8, 128)


# -- fixed-size leaves whose row is a matrix: read and written in turn --------


class _MatrixRows:
    """The least model with a window leaf beside a fixed-size leaf whose
    row is a matrix `[4, 8, 128]` (16 KiB): an event adds its value to
    every element of its row's matrix and appends it to its context; the
    score is the matrix's first element before the event."""

    streaming = True
    windows = {"far": "pos"}
    step_stats = ()

    class cfg:
        window = 3

    def init(self, rng):
        import jax.numpy as jnp

        return {"one": jnp.ones(())}

    def init_state(self, cap):
        import jax.numpy as jnp

        return {"pos": jnp.zeros(cap, jnp.int32),
                "far": jnp.zeros((cap, 8, 128), jnp.float32),
                "m": jnp.zeros((cap, 4, 8, 128), jnp.float32)}

    def step_score(self, params, rows, v, live):
        import jax.numpy as jnp

        m = rows["m"].read(v)
        score = rows["m"].write(m + v[:, None, None, None], m[:, 0, 0, 0])
        entry = jnp.broadcast_to(v[:, None], (v.shape[0], 128))
        return score * params["one"], {"pos": rows["pos"] + 1,
                                       "far": entry}, None

    def warm_state(self, params, x, valid):
        import jax.numpy as jnp

        state = self.init_state(x.shape[0])
        count = valid.sum(1)
        state["pos"] = count.astype(jnp.int32)
        state["m"] = state["m"] + jnp.where(valid, x, 0).sum(1)[
            :, None, None, None]
        return state


@pytest.mark.parametrize("slice_bytes", [1 << 19, 4096],
                         ids=["whole_rows", "rows_in_blocks"])
def test_matrix_rows_are_written_where_named_and_counted_live(
        monkeypatch, slice_bytes):
    """A fixed-size leaf of three or more dimensions reaches the step as
    a `RowsInTurn`; heavier than one gathered slice its rows are read in
    blocks of their leading dimension. The rows written are the rows
    named, padding reads the scratch row and writes nothing, and
    `rewritten_bytes` counts the live rows of the leaves that are no
    window."""
    import jax

    from sitewhere_tpu.scoring import stream

    monkeypatch.setattr(stream, "GATHER_SLICE_BYTES", slice_bytes)
    model = _MatrixRows()
    ring = StreamingRing(model, capacity=6, initial_floor=6)
    ring.bind_params(model.init(jax.random.PRNGKey(0)))
    assert ring.row_bytes == 4 + 4 * 8 * 128 * 4      # `pos` and `m`
    ring.load(np.tile(np.float32([1, 2, 3]), (6, 1)), np.full(6, 3))
    dev = np.asarray([1, 4, 5], np.int32)
    for k, value in enumerate((10.0, 100.0)):
        out = np.asarray(ring.update_and_score(
            model, ring._params, dev, np.full(3, value, np.float32), 4))
        assert (out[:3] == (6.0, 16.0)[k]).all()
    m = np.asarray(ring.state["m"])
    assert (m[[1, 4, 5]] == 116.0).all() and (m[[0, 2, 3]] == 6.0).all()
    assert (m[6] == 0).all()                           # the scratch row
    far = np.asarray(ring.state["far"])
    assert (far[[1, 4, 5], 3:5, 0] == [10.0, 100.0]).all()
    assert not far[[0, 2, 3, 6]].any()
    assert ring.rewritten_bytes == 2 * 3 * ring.row_bytes
    fn = ring._fns[ring.capacity, 4]
    text = fn.lower(ring._params, ring.state, *ring._pad(
        dev, np.zeros(3, np.float32), 4)).as_text()
    assert ("tensor<7x4x1x8x128xf32>" in text) == (slice_bytes == 4096)
    assert text.count("optimization_barrier") == 2     # a read, a write
