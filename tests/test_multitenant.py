"""Config 4 [BASELINE.json]: multi-tenant ingest with per-tenant model
sharding on a TPU mesh (scaled down onto the 8-device CPU test mesh).

Covers:
- TenantStack: stacked-params correctness vs per-tenant scoring, slot
  reuse, hot-swap versioning, mesh-sharded == unsharded numerics;
- SharedScoringPool: cross-tenant flush rounds, per-tenant thresholds
  and delivery;
- e2e: N tenants with `shared: true` rule-processing over a (data=4,
  model=2) mesh, one vmapped XLA call scoring all tenants per flush.
"""

import asyncio

import jax
import numpy as np

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.domain.model import DeviceType
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.kernel.service import ServiceRuntime
from sitewhere_tpu.models import build_model
from sitewhere_tpu.parallel.mesh import make_mesh
from sitewhere_tpu.parallel.tenant_stack import TenantStack
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.pool import PoolConfig, SharedScoringPool
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

from tests.test_pipeline import wait_until


def _rand_windows(rng, n, w):
    x = rng.normal(20.0, 2.0, (n, w)).astype(np.float32)
    return x, np.ones((n, w), bool)


def test_tenant_stack_matches_per_tenant_scoring():
    model = build_model("lstm", window=16, hidden=8)
    stack = TenantStack(model, mesh=None)
    rng = np.random.default_rng(0)
    params = {t: model.init(jax.random.PRNGKey(10 + i))
              for i, t in enumerate(["a", "b", "c"])}
    for t, p in params.items():
        stack.add_tenant(t, p)
    assert stack.capacity == 4  # pow2 ≥ 3

    x, v = _rand_windows(rng, stack.pad_batch(32), 16)
    xs = np.broadcast_to(x, (stack.capacity, *x.shape)).copy()
    vs = np.broadcast_to(v, (stack.capacity, *v.shape)).copy()
    scores = np.asarray(stack.score(xs, vs))
    for t, p in params.items():
        ref = np.asarray(jax.jit(model.score)(p, x, v))
        np.testing.assert_allclose(scores[stack.slots[t]], ref,
                                   rtol=1e-4, atol=1e-5)


def test_tenant_stack_mesh_sharded_equals_unsharded():
    model = build_model("lstm", window=16, hidden=8)
    mesh = make_mesh(data=4, model=2)
    plain = TenantStack(model, mesh=None)
    sharded = TenantStack(model, mesh=mesh)
    params = [model.init(jax.random.PRNGKey(i)) for i in range(3)]
    for i, p in enumerate(params):
        plain.add_tenant(f"t{i}", p)
        sharded.add_tenant(f"t{i}", p)
    assert sharded.capacity % 2 == 0  # multiple of model axis

    rng = np.random.default_rng(1)
    b = sharded.pad_batch(24)  # multiple of data axis
    x = rng.normal(20, 2, (sharded.capacity, b, 16)).astype(np.float32)
    v = np.ones_like(x, bool)
    out_sharded = np.asarray(sharded.score(x, v))
    out_plain = np.asarray(plain.score(x[: plain.capacity], v[: plain.capacity]))
    np.testing.assert_allclose(out_sharded[:3], out_plain[:3],
                               rtol=1e-4, atol=1e-5)


def test_tenant_stack_swap_grow_and_slot_reuse():
    model = build_model("lstm", window=16, hidden=8)
    stack = TenantStack(model)
    stack.add_tenant("a")
    stack.add_tenant("b")
    assert stack.capacity == 2
    stack.add_tenant("c")  # crosses pow2 → grow
    assert stack.capacity == 4

    p_new = model.init(jax.random.PRNGKey(99))
    assert stack.versions["b"] == 0
    assert stack.set_params("b", p_new) == 1
    got = stack.get_params("b")
    ref_leaves = jax.tree.leaves(p_new)
    got_leaves = jax.tree.leaves(got)
    for g, r in zip(got_leaves, ref_leaves):
        np.testing.assert_allclose(g, r, rtol=1e-6)

    slot_b = stack.slots["b"]
    stack.remove_tenant("b")
    assert stack.add_tenant("d") == slot_b  # freed slot reused
    assert stack.capacity == 4
    # the reused slot must be reset to init params — not leak b's
    # swapped-in trained weights to the new tenant
    got_d = jax.tree.leaves(stack.get_params("d"))
    init_leaves = jax.tree.leaves(stack._init_params)
    swapped_leaves = jax.tree.leaves(p_new)
    assert any(not np.allclose(g, s)
               for g, s in zip(got_d, swapped_leaves))
    for g, r in zip(got_d, init_leaves):
        np.testing.assert_allclose(g, r, rtol=1e-6)


def test_shared_pool_flushes_all_tenants_in_one_call(run):
    async def main():
        model = build_model("zscore", window=16)
        pool = SharedScoringPool(
            model, MetricsRegistry(),
            PoolConfig(batch_buckets=(16, 64), batch_window_ms=1.0))
        delivered: dict[str, list] = {"a": [], "b": [], "c": []}
        sims, stores = {}, {}
        # c's threshold sits above the zscore clip (50) → never alerts
        for tid, thr in [("a", 4.0), ("b", 4.0), ("c", 51.0)]:
            store = TelemetryStore(history=32)
            sim = DeviceSimulator(SimConfig(num_devices=20, seed=5), tenant_id=tid)
            for k in range(20):
                batch, _ = sim.tick(t=60.0 * k)
                store.append_measurements(batch)

            async def deliver(scored, tid=tid):
                delivered[tid].append(scored)

            pool.register(tid, store, thr, deliver)
            sims[tid], stores[tid] = sim, store
        await wait_until(lambda: pool.ready, timeout=30.0)

        # inject a huge spike for every device in every tenant
        for tid, sim in sims.items():
            sim.cfg = SimConfig(num_devices=20, seed=5, anomaly_rate=1.0,
                                anomaly_magnitude=30.0)
            batch, truth = sim.tick(t=21 * 60.0)
            assert truth.all()
            stores[tid].append_measurements(batch)
            pool.admit(tid, batch)
        before_rounds = pool.flush_rounds.value
        await wait_until(
            lambda: all(len(v) > 0 for v in delivered.values()), timeout=10.0)

        # all three tenants scored in one stacked round
        assert pool.flush_rounds.value == before_rounds + 1
        a, b, c = (delivered[t][0] for t in "abc")
        assert len(a) == len(b) == len(c) == 20
        # same data, same model → per-tenant thresholds differentiate
        assert a.is_anomaly.all() and b.is_anomaly.all()
        assert not c.is_anomaly.any()
        pool.close()

    run(main())


def test_e2e_multitenant_pooled_scoring(run):
    """Scaled-down config 4: 4 tenants × 50 devices over a (4, 2) mesh,
    pooled scoring, per-tenant model alerts."""

    from sitewhere_tpu.services import (
        DeviceManagementService,
        DeviceStateService,
        EventManagementService,
        EventSourcesService,
        InboundProcessingService,
        RuleProcessingService,
    )

    async def main():
        rt = ServiceRuntime(InstanceSettings(instance_id="mt"))
        for cls in (DeviceManagementService, EventSourcesService,
                    InboundProcessingService, EventManagementService,
                    DeviceStateService, RuleProcessingService):
            rt.add_service(cls(rt))
        await rt.start()
        tenants = [f"t{i}" for i in range(4)]
        rp_section = {
            "model": "zscore", "model_config": {"window": 16},
            "threshold": 5.0, "batch_window_ms": 1.0,
            "shared": True, "mesh": {"data": 4, "model": 2},
            "buckets": [64, 256],
        }
        for tid in tenants:
            await rt.add_tenant(TenantConfig(
                tenant_id=tid,
                sections={"rule-processing": rp_section,
                          "event-management": {"history": 64}}))
            dm = rt.api("device-management").management(tid)
            dm.bootstrap_fleet(DeviceType(token="thermo", name="T"), 50)

        rp = rt.api("rule-processing")
        pool = rp.engine(tenants[0]).pool_slot.pool
        # all four tenants share one pool/stack
        assert all(rp.engine(t).pool_slot.pool is pool for t in tenants)
        assert set(pool.stack.slots) == set(tenants)
        await wait_until(lambda: pool.ready, timeout=60.0)

        sims = {t: DeviceSimulator(SimConfig(num_devices=50, seed=3), tenant_id=t)
                for t in tenants}
        receivers = {t: rt.api("event-sources").engine(t).receiver("default")
                     for t in tenants}
        for k in range(24):
            for t in tenants:
                await receivers[t].submit(sims[t].payload(t=60.0 * k)[0])
        for t in tenants:
            em = rt.api("event-management").management(t)
            await wait_until(
                lambda em=em: em.telemetry.total_events == 24 * 50, timeout=20.0)
        # drain history scoring before injecting anomalies
        await wait_until(lambda: pool.flights.latency.count >= 4 * 24 * 50, timeout=60.0)

        # partial-window z-scores can legitimately alert during history
        # (e.g. a sine swing over an 8-sample window); only alerts raised
        # after the injection are asserted against the truth mask
        n_before = {t: len(rt.api("event-management").management(t).list_alerts())
                    for t in tenants}
        truths = {}
        for t in tenants:
            sims[t].cfg = SimConfig(num_devices=50, seed=3, anomaly_rate=0.2,
                                    anomaly_magnitude=20.0)
            payload, truth = sims[t].payload(t=25 * 60.0)
            truths[t] = truth
            await receivers[t].submit(payload)

        for t in tenants:
            em = rt.api("event-management").management(t)
            n_true = int(truths[t].sum())
            assert n_true > 0
            await wait_until(
                lambda em=em, n=n_true + n_before[t]: len(em.list_alerts()) >= n,
                timeout=30.0)
            alerts = em.list_alerts()[n_before[t]:]
            assert all(a.source == "model" for a in alerts)
            dm = rt.api("device-management").management(t)
            alert_devices = {dm.get_device(a.device_id).index for a in alerts}
            assert alert_devices == set(np.nonzero(truths[t])[0].tolist())
            # scored events observable per tenant
            scored_topic = rt.naming.tenant_topic(t, "scored-events")
            assert sum(rt.bus.end_offsets(scored_topic)) > 0
        await rt.stop()

    run(main())
