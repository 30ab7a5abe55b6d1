"""Cross-tenant megabatched scoring (scoring/pool.py): ISSUE 8's
acceptance tests.

- wiring/config: `rule-processing: {megabatch: {enabled}}` routes every
  tenant of one architecture through ONE shared stacked-params pool
  (dedicated sessions stay the default), with the configured megabatch
  window and tenants-per-dispatch bound.
- on/off equivalence: megabatch-on vs megabatch-off runs of the same
  event sequence produce identical per-tenant scores, persisted
  telemetry, alerts, and committed offsets — megabatching changes the
  dispatch count, never behavior — AND the on-leg's flush-path jit
  dispatch count collapses.
- version fence: a param hot-swap landing while a megabatch is in
  flight attributes that batch to the weights that scored it (the
  version snapshotted at dispatch), never the fresher one.
- lifecycle under load: tenant register (stack growth + rebuild
  accounting) and unregister (pending accounted as dropped) while other
  tenants keep scoring.
- `max_tenants` bounds tenants packed per stacked dispatch; leftovers
  flush the next round, nothing is lost.
- chaos: `scoring.megabatch` faults quarantine the admitting record to
  the tenant DLQ with provenance; later records score normally.
"""

import asyncio
import contextlib

import numpy as np

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.domain.model import DeviceType
from sitewhere_tpu.kernel.bus import TopicNaming
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.kernel.service import ServiceRuntime
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.pool import PoolConfig, SharedScoringPool
from sitewhere_tpu.services import (
    DeviceManagementService,
    DeviceStateService,
    EventManagementService,
    EventSourcesService,
    InboundProcessingService,
    RuleProcessingService,
)
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig
from tests.test_pipeline import wait_until

RULE = {"model": "zscore", "model_config": {"window": 16},
        "threshold": 6.0, "batch_window_ms": 1.0,
        "buckets": [256], "capacity": 256}

TENANTS = ("t0", "t1", "t2", "t3")


@contextlib.asynccontextmanager
async def megabatch_runtime(tenants=TENANTS, megabatch=True,
                            num_devices=32, faults=None,
                            instance_id="mb", rule_extra=None):
    """Full pipeline with N tenants, each `megabatch: {enabled}` pinned
    (True = the shared stacked-dispatch pool, False = dedicated
    per-tenant sessions — the A/B legs)."""
    rt = ServiceRuntime(InstanceSettings(instance_id=instance_id))
    for cls in (DeviceManagementService, EventSourcesService,
                InboundProcessingService, EventManagementService,
                DeviceStateService, RuleProcessingService):
        rt.add_service(cls(rt))
    if faults is not None:
        rt.install_faults(faults)
    await rt.start()
    for tid in tenants:
        rule = {**RULE, "megabatch": {"enabled": megabatch},
                **(rule_extra or {})}
        await rt.add_tenant(TenantConfig(tenant_id=tid,
                                         sections={"rule-processing": rule}))
        dm = rt.api("device-management").management(tid)
        dm.bootstrap_fleet(DeviceType(token="thermo", name="T"),
                           num_devices)
    for tid in tenants:
        eng = rt.api("rule-processing").engine(tid)
        sink = eng.session or eng.pool_slot
        await wait_until(lambda s=sink: s.ready, timeout=60.0)
    try:
        yield rt
    finally:
        await rt.stop()


async def _drive_tenants(rt, tenants=TENANTS, n_dev=32, ticks=10,
                         anomaly_rate=0.05):
    """Feed every tenant the same per-tenant-seeded sequence; return
    {tenant: (scored map, telemetry total, alert set, committed)} once
    everything drained and committed — the observables the on/off legs
    must agree on."""
    consumers = {tid: rt.bus.subscribe(
        rt.naming.tenant_topic(tid, TopicNaming.SCORED_EVENTS),
        group="mb-test-meter") for tid in tenants}
    sims = {tid: DeviceSimulator(
        SimConfig(num_devices=n_dev, seed=100 + i,
                  anomaly_rate=anomaly_rate, anomaly_magnitude=15.0),
        tenant_id=tid) for i, tid in enumerate(tenants)}
    receivers = {tid: rt.api("event-sources").engine(tid)
                 .receiver("default") for tid in tenants}
    for k in range(ticks):
        for tid in tenants:
            payload, _ = sims[tid].payload(t=1000.0 + 60.0 * k)
            assert await receivers[tid].submit(payload)
    expected = n_dev * ticks
    out = {}
    for tid in tenants:
        em = rt.api("event-management").management(tid)
        await wait_until(
            lambda em=em: em.telemetry.total_events >= expected,
            timeout=30.0)
        scored: dict = {}

        def collect(c=consumers[tid], scored=scored):
            for r in c.poll_nowait(max_records=512):
                b = r.value
                for i in range(len(b)):
                    scored[(int(b.device_index[i]), float(b.ts[i]))] = (
                        round(float(b.score[i]), 3),
                        bool(b.is_anomaly[i]))
            return len(scored) >= expected

        await wait_until(collect, timeout=30.0)
        consumers[tid].close()
        dm = rt.api("device-management").management(tid)
        alerts = {(dm.get_device(a.device_id).token, float(a.event_date),
                   a.type) for a in em.spi.alerts}
        decoded = rt.naming.tenant_topic(
            tid, TopicNaming.EVENT_SOURCE_DECODED)
        end_total = sum(rt.bus.end_offsets(decoded))
        group = rt.bus._groups[f"{tid}.inbound-processing"]

        def committed_total(group=group, decoded=decoded):
            return sum(off for (topic, _p), off in group.committed.items()
                       if topic == decoded)

        await wait_until(
            lambda c=committed_total, e=end_total: c() >= e, timeout=30.0)
        out[tid] = (scored, em.telemetry.total_events, alerts,
                    committed_total())
    return out


# -- wiring / config --------------------------------------------------------

def test_megabatch_wiring_and_config(run):
    async def main():
        async with megabatch_runtime(instance_id="mb-w") as rt:
            rp = rt.api("rule-processing")
            engines = [rp.engine(t) for t in TENANTS]
            # every tenant rides the pool, no dedicated sessions
            assert all(e.session is None for e in engines)
            pool = engines[0].pool_slot.pool
            assert all(e.pool_slot.pool is pool for e in engines)
            assert set(pool.stack.slots) == set(TENANTS)
            # megabatch window: instance default 1.0 ms (≤1 ms of
            # batching latency for the dispatch collapse)
            assert pool.cfg.window_s == 0.001
            # pool inflight bound plumbed from the tenant config
            assert pool.cfg.max_inflight == 64
        # tenant override beats the instance default
        async with megabatch_runtime(
                tenants=("solo",), instance_id="mb-wo",
                rule_extra={"megabatch": {"enabled": True,
                                          "window_ms": 4.0,
                                          "max_tenants": 2}}) as rt:
            pool = rt.api("rule-processing").engine("solo").pool_slot.pool
            assert pool.cfg.window_s == 0.004
            assert pool.cfg.max_tenants == 2
        # megabatch off → dedicated sessions (the default path)
        async with megabatch_runtime(megabatch=False,
                                     instance_id="mb-wn") as rt:
            engines = [rt.api("rule-processing").engine(t) for t in TENANTS]
            assert all(e.session is not None and e.pool_slot is None
                       for e in engines)

    run(main())


# -- equivalence + the dispatch collapse ------------------------------------

def test_megabatch_on_off_equivalence_and_dispatch_collapse(run):
    """The acceptance pair: identical per-tenant observables, collapsed
    jit dispatch count."""
    async def main():
        async with megabatch_runtime(megabatch=True,
                                     instance_id="mb-on") as rt:
            on = await _drive_tenants(rt)
            on_disp = rt.metrics.counter("scoring.dispatches").value
            on_mb = rt.metrics.counter("scoring.megabatch_dispatches").value
            on_tpd = rt.metrics.histogram(
                "scoring.megabatch_tenants_per_dispatch")
            # stacked dispatches happened, and they aggregated tenants
            assert on_mb > 0 and on_mb == on_disp
            assert on_tpd._max > 1.0
        async with megabatch_runtime(megabatch=False,
                                     instance_id="mb-off") as rt:
            off = await _drive_tenants(rt)
            off_disp = rt.metrics.counter("scoring.dispatches").value
            assert rt.metrics.counter(
                "scoring.megabatch_dispatches").value == 0
        for tid in TENANTS:
            scored_on, total_on, alerts_on, committed_on = on[tid]
            scored_off, total_off, alerts_off, committed_off = off[tid]
            assert total_on == total_off == 32 * 10
            assert scored_on.keys() == scored_off.keys()
            for key, val in scored_on.items():
                assert scored_off[key] == val, (tid, key)
            assert alerts_on == alerts_off and alerts_on
            assert committed_on == committed_off > 0
        # the point of the exercise: four tenants' flush rounds fused
        # into stacked dispatches — at 4 tenants the per-round ideal is
        # 4×; scheduling jitter may split rounds, so assert ≥2×
        assert on_disp * 2 <= off_disp, (on_disp, off_disp)

    run(main())


# -- version fence ----------------------------------------------------------

def _batch(tid: str, n: int = 8, t: float = 10.0,
           value: float = 21.0) -> MeasurementBatch:
    return MeasurementBatch(
        BatchContext(tenant_id=tid, source="test"),
        np.arange(n, dtype=np.uint32), np.zeros(n, np.uint16),
        np.full(n, value, np.float32), np.full(n, t))


def test_param_hot_swap_version_fence(run):
    """A swap landing after dispatch but before settle must not steal
    the in-flight megabatch's attribution: the settled batch carries
    the version snapshotted at dispatch."""
    async def main():
        model = build_model("lstm", window=16, hidden=8)
        pool = SharedScoringPool(
            model, MetricsRegistry(),
            PoolConfig(batch_buckets=(32,), batch_window_ms=50.0))
        delivered: list = []

        async def deliver(scored):
            delivered.append(scored)

        slot = pool.register("a", TelemetryStore(history=32), 6.0, deliver)
        await wait_until(lambda: pool.ready, timeout=60.0)
        fence0 = pool.stack.fence
        # admit + dispatch in ONE loop step (no awaits), so the
        # background flusher cannot race this round
        slot.admit(_batch("a"))
        pool._flush_round()
        # the swap lands while the dispatch is in flight (its settle
        # task exists but has not run yet)
        new_version = slot.swap_params(
            model.init(__import__("jax").random.PRNGKey(7)))
        assert new_version == 1
        assert pool.stack.fence > fence0
        await wait_until(lambda: len(delivered) == 1, timeout=30.0)
        # fence holds: attribution is the DISPATCH-time version
        assert delivered[0].model_version == 0
        # post-swap dispatches attribute to the new weights
        slot.admit(_batch("a", t=11.0))
        pool._flush_round()
        await wait_until(lambda: len(delivered) == 2, timeout=30.0)
        assert delivered[1].model_version == 1
        pool.close()

    run(main())


# -- tenant add/remove under load -------------------------------------------

def test_reload_history_that_grows_the_ring_rewarms_behind_the_gate(run):
    """The pool sizes its ring from the host store at register time. A
    store that grew afterwards (bootstrapped fleet, bulk import) grows
    the ring at `reload_history` — which must close the ready gate and
    recompile there, not leave the next flush to compile on the hot
    path (on a v5e that stall was long enough to shed frames)."""
    async def main():
        pool = SharedScoringPool(
            build_model("zscore", window=8), MetricsRegistry(),
            PoolConfig(batch_buckets=(32,), batch_window_ms=50.0))

        async def deliver(_scored) -> None:
            return None

        store = TelemetryStore(history=16)
        slot = pool.register("a", store, 6.0, deliver)
        await wait_until(lambda: pool.ready)
        assert pool.ring.device_cap == 1024
        n = 1500                                  # outgrows the ring
        for k in range(10):
            store.append_measurements(MeasurementBatch(
                BatchContext(tenant_id="a", source="import"),
                np.arange(n, dtype=np.uint32), np.zeros(n, np.uint16),
                np.full(n, 20.0 + k, np.float32), np.full(n, float(k))))
        slot.reload_history()
        assert pool.ring.device_cap == 2048
        assert not pool.ready                     # gate closed: re-warming
        await wait_until(lambda: pool.ready)
        assert pool._warmed_key == pool._current_key()
        # a reseed at unchanged shapes leaves the gate alone
        slot.reload_history()
        assert pool.ready
        pool.close()

    run(main())


def test_tenant_add_remove_under_load(run):
    async def main():
        metrics = MetricsRegistry()
        model = build_model("zscore", window=16)
        pool = SharedScoringPool(
            model, metrics, PoolConfig(batch_buckets=(32,),
                                       batch_window_ms=0.5))
        got: dict[str, int] = {}

        def deliver_for(tid):
            async def deliver(scored):
                got[tid] = got.get(tid, 0) + len(scored)
            return deliver

        for tid in ("a", "b"):
            pool.register(tid, TelemetryStore(history=32), 6.0,
                          deliver_for(tid))
        await wait_until(lambda: pool.ready, timeout=60.0)
        for tid in ("a", "b"):
            pool.admit(tid, _batch(tid))
        pool._flush_round()  # in flight for a+b
        # register c mid-flight: stack grows 2 → 4 (a rebuild), the
        # in-flight settle still lands
        pool.register("c", TelemetryStore(history=32), 6.0,
                      deliver_for("c"))
        assert pool.stack.capacity == 4
        assert metrics.counter("scoring.stack_rebuilds").value >= 1
        assert pool.stack.occupancy().sum() == 3
        await wait_until(lambda: got.get("a") == 8 and got.get("b") == 8,
                         timeout=30.0)
        await wait_until(lambda: pool.ready, timeout=60.0)
        # unregister b WITH pending: its events are accounted dropped,
        # the others keep scoring
        pool.admit("b", _batch("b", t=20.0))
        pending_b = pool.tenants["b"].pending_n
        assert pending_b == 8
        pool.unregister("b")
        assert metrics.counter(
            "scoring.admissions_dropped").value >= pending_b
        assert pool.stack.occupancy().sum() == 2
        for tid in ("a", "c"):
            pool.admit(tid, _batch(tid, t=21.0))
        pool._flush_round()
        await wait_until(lambda: got.get("a") == 16 and got.get("c") == 8,
                         timeout=30.0)
        assert "b" not in pool.stack.slots
        pool.close()

    run(main())


# -- max_tenants bound ------------------------------------------------------

def test_max_tenants_bounds_each_dispatch(run):
    async def main():
        metrics = MetricsRegistry()
        model = build_model("zscore", window=16)
        pool = SharedScoringPool(
            model, metrics, PoolConfig(batch_buckets=(32,),
                                       batch_window_ms=50.0,
                                       max_tenants=2))
        got: dict[str, int] = {}

        def deliver_for(tid):
            async def deliver(scored):
                got[tid] = got.get(tid, 0) + len(scored)
            return deliver

        tids = ("a", "b", "c", "d")
        for tid in tids:
            pool.register(tid, TelemetryStore(history=32), 6.0,
                          deliver_for(tid))
        await wait_until(lambda: pool.ready, timeout=60.0)
        for tid in tids:
            pool.admit(tid, _batch(tid))
        pool._flush_round()   # packs 2 tenants, re-arms the wake
        pool._flush_round()   # the other 2
        assert pool.megabatch_tenants._max <= 2.0
        await wait_until(lambda: all(got.get(t) == 8 for t in tids),
                         timeout=30.0)
        assert pool._total_pending == 0
        pool.close()

    run(main())


# -- chaos ------------------------------------------------------------------

def test_megabatch_chaos_quarantines_with_provenance(run):
    """An injected `scoring.megabatch` fault at admission dead-letters
    the admitting record with provenance; the pool (and its flusher)
    survive, and later records score normally."""
    async def main():
        from sitewhere_tpu.kernel.dlq import list_dead_letters
        from sitewhere_tpu.kernel.faults import FaultInjector

        fi = FaultInjector(seed=5)
        async with megabatch_runtime(tenants=("t0",), faults=fi,
                                     instance_id="mb-ch") as rt:
            fi.arm("scoring.megabatch", rate=1.0, max_faults=1)
            decoded = rt.naming.tenant_topic(
                "t0", TopicNaming.EVENT_SOURCE_DECODED)
            dlq = rt.naming.tenant_topic("t0", TopicNaming.DEAD_LETTER)
            scored_topic = rt.naming.tenant_topic(
                "t0", TopicNaming.SCORED_EVENTS)
            await rt.bus.produce(decoded, _batch("t0", n=16, t=1000.0),
                                 key="gw")
            await wait_until(
                lambda: len(list_dead_letters(rt.bus, dlq)) >= 1,
                timeout=15.0)
            entries = list_dead_letters(rt.bus, dlq)
            assert len(entries) == 1
            # quarantined by the admitting consumer lane (fused fast
            # lane or staged rule processor), with its provenance
            assert any(s in entries[0][1]["stage"]
                       for s in ("fastlane", "rule-processor"))
            assert entries[0][1]["original_topic"] == decoded
            # the fault is spent: later records admit + score normally
            consumer = rt.bus.subscribe(scored_topic, group="mb-ch-meter")
            await rt.bus.produce(decoded, _batch("t0", n=16, t=1060.0),
                                 key="gw")
            seen = []

            def collect():
                seen.extend(consumer.poll_nowait(max_records=64))
                return sum(len(r.value) for r in seen) >= 16
            await wait_until(collect, timeout=15.0)
            consumer.close()

    run(main())


# -- settle-task retention (swx lint TSK01 regression) -----------------------


def test_settle_task_retained_until_delivery(run):
    """The in-flight settle task is strongly referenced: the event loop
    keeps only a weak ref, so the pre-fix dropped handle could be GC'd
    mid-flight — wedging `inflight`/`_outstanding` forever with the
    megabatch never settling."""
    async def main():
        model = build_model("zscore", window=16)
        pool = SharedScoringPool(
            model, MetricsRegistry(),
            PoolConfig(batch_buckets=(32,), batch_window_ms=50.0))
        delivered: list = []

        async def deliver(scored):
            delivered.append(scored)

        slot = pool.register("a", TelemetryStore(history=32), 6.0, deliver)
        await wait_until(lambda: pool.ready, timeout=60.0)
        slot.admit(_batch("a"))
        pool._flush_round()
        assert len(pool.flights.tasks) == 1  # strong ref while in flight
        await wait_until(lambda: len(delivered) == 1, timeout=30.0)
        await wait_until(lambda: not pool.flights.tasks, timeout=5.0)
        pool.close()

    run(main())


def test_settle_task_failure_is_logged(run, caplog):
    """An escaped settle exception is retrieved and surfaced by the
    supervisor callback instead of dying unretrieved."""
    import logging

    from sitewhere_tpu.scoring.settle import Flights

    async def main():
        flights = Flights.__new__(Flights)
        flights.tasks = set()

        async def boom():
            raise RuntimeError("settle exploded")

        task = asyncio.get_running_loop().create_task(boom())
        flights.tasks.add(task)
        task.add_done_callback(flights.task_done)
        while flights.tasks:
            await asyncio.sleep(0)

    with caplog.at_level(logging.ERROR, logger="sitewhere_tpu.scoring.settle"):
        run(main())
    assert any("settle task died" in r.getMessage() for r in caplog.records)
