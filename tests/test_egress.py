"""Sharded egress fast lanes (kernel/egresslane.py): ISSUE 6's
acceptance tests.

- wiring/config: every engine with a model builds its egress stage, and
  the tenant `egress: {lanes}` section shards BOTH the egress stage and
  the consumer lanes.
- lane-count equivalence: `lanes=1` vs `lanes=4` runs of the same event
  sequence produce identical scored events, persisted telemetry,
  alerts, and committed offsets — shard count changes concurrency,
  never behavior.
- alert emission off the flush path: counted (`rules.alerts_emitted`),
  and an alert-path failure can never block a scoring flush.
- chaos: `egress.publish` faults quarantine the scored batch to the
  tenant DLQ with egress provenance (replayable onto the scored
  topic); crash faults on the sharded consumer loops are healed by the
  supervisor and the pipeline still drains.
"""

import asyncio
import contextlib

import numpy as np

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.domain.model import DeviceType
from sitewhere_tpu.kernel.bus import TopicNaming
from sitewhere_tpu.kernel.service import ServiceRuntime
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


@contextlib.asynccontextmanager
async def egress_runtime(num_devices=32, fastlane=None, egress=None,
                         faults=None, instance_id="eg"):
    """Full pipeline runtime with tenant 'acme'; `egress` is the tenant
    `egress:` section ({lanes}), `fastlane` pins the ingress
    lane via its override (None = auto-detection)."""
    rt = ServiceRuntime(InstanceSettings(instance_id=instance_id))
    for cls in (DeviceManagementService, EventSourcesService,
                InboundProcessingService, EventManagementService,
                DeviceStateService, RuleProcessingService):
        rt.add_service(cls(rt))
    if faults is not None:
        rt.install_faults(faults)
    await rt.start()
    sections = {"rule-processing": dict(RULE)}
    if fastlane is not None:
        sections["fastlane"] = {"enabled": fastlane}
    if egress is not None:
        sections["egress"] = dict(egress)
    await rt.add_tenant(TenantConfig(tenant_id="acme", sections=sections))
    dm = rt.api("device-management").management("acme")
    dm.bootstrap_fleet(DeviceType(token="thermo", name="T"), num_devices)
    session = rt.api("rule-processing").engine("acme").session
    await wait_until(lambda: session.ready, timeout=60.0)
    try:
        yield rt
    finally:
        await rt.stop()


def _measurements(n: int, t: float, start: int = 0,
                  value: float = 21.0) -> MeasurementBatch:
    return MeasurementBatch(
        BatchContext(tenant_id="acme", source="test"),
        np.arange(start, start + n, dtype=np.uint32),
        np.zeros(n, np.uint16), np.full(n, value, np.float32),
        np.full(n, t))


async def _drive(rt, n_sim=48, ticks=12, anomaly_rate=0.05):
    """Feed `ticks` simulator payloads and return the run's observable
    outputs: scored {(device, ts) -> (score, anomaly)}, telemetry
    total, alert set, and the decoded-topic group's committed offsets
    (summed per partition) once everything has drained and committed."""
    scored_topic = rt.naming.tenant_topic("acme", TopicNaming.SCORED_EVENTS)
    consumer = rt.bus.subscribe(scored_topic, group="egress-test-meter")
    sim = DeviceSimulator(SimConfig(num_devices=n_sim, seed=11,
                                    anomaly_rate=anomaly_rate,
                                    anomaly_magnitude=15.0),
                          tenant_id="acme")
    receiver = rt.api("event-sources").engine("acme").receiver("default")
    for k in range(ticks):
        payload, _ = sim.payload(t=1000.0 + 60.0 * k)
        assert await receiver.submit(payload)
    expected = 32 * ticks  # only the registered 32 of n_sim score
    em = rt.api("event-management").management("acme")
    await wait_until(lambda: em.telemetry.total_events >= expected,
                     timeout=30.0)
    scored = {}

    def collect():
        for r in consumer.poll_nowait(max_records=512):
            b = r.value
            for i in range(len(b)):
                scored[(int(b.device_index[i]), float(b.ts[i]))] = (
                    round(float(b.score[i]), 3), bool(b.is_anomaly[i]))
        return len(scored) >= expected

    await wait_until(collect, timeout=30.0)
    consumer.close()
    # device_id is a per-run UUID; the bootstrap token (`dev-{i}`) is
    # the stable cross-run identity
    dm = rt.api("device-management").management("acme")
    alerts = {(dm.get_device(a.device_id).token, float(a.event_date),
               a.type, a.message) for a in em.spi.alerts}
    # the decoded-topic group commits via the shared checkpoint barrier
    # once everything settled AND published; wait for it to catch up
    decoded = rt.naming.tenant_topic("acme",
                                     TopicNaming.EVENT_SOURCE_DECODED)
    end_total = sum(rt.bus.end_offsets(decoded))
    group = rt.bus._groups["acme.inbound-processing"]

    def committed_total():
        return sum(off for (topic, _p), off in group.committed.items()
                   if topic == decoded)

    await wait_until(lambda: committed_total() >= end_total, timeout=30.0)
    return scored, em.telemetry.total_events, alerts, committed_total()


# -- wiring / config --------------------------------------------------------

def test_egress_wiring_and_lane_config(run):
    async def main():
        # 1 lane by default; session sink IS the stage
        async with egress_runtime(instance_id="eg-w1") as rt:
            eng = rt.api("rule-processing").engine("acme")
            assert eng.egress is not None and eng.egress.lanes == 1
            assert eng.session.sink is eng.egress
            assert len(eng.fastlanes) == 1
        # lanes=4 shards the egress stage AND the ingress fast lane;
        # every shard loop is a supervised child of the engine
        async with egress_runtime(egress={"lanes": 4},
                                  instance_id="eg-w4") as rt:
            eng = rt.api("rule-processing").engine("acme")
            assert eng.egress.lanes == 4
            assert len(eng.egress.shards) == 4
            assert len(eng.fastlanes) == 4
            assert len({lane.name for lane in eng.fastlanes}) == 4
        # lanes also shard the STAGED lane's consumers
        async with egress_runtime(fastlane=False, egress={"lanes": 3},
                                  instance_id="eg-ws") as rt:
            inb = rt.services["inbound-processing"].engines["acme"]
            assert len(inb.processors) == 3
            emg = rt.services["event-management"].engines["acme"]
            assert len(emg.persisters) == 3

    run(main())


# -- equivalence ------------------------------------------------------------

def test_lane_count_equivalence(run):
    """lanes=1 vs lanes=4: identical scored events, persisted
    telemetry, alerts, and committed offsets — sharding changes
    concurrency, never behavior."""
    async def main():
        async with egress_runtime(egress={"lanes": 1},
                                  instance_id="eg-l1") as rt:
            one = await _drive(rt)
        async with egress_runtime(egress={"lanes": 4},
                                  instance_id="eg-l4") as rt:
            four = await _drive(rt)
        scored_1, total_1, alerts_1, committed_1 = one
        scored_4, total_4, alerts_4, committed_4 = four
        assert total_1 == total_4 == 32 * 12
        assert scored_1.keys() == scored_4.keys()
        assert len(scored_1) == 32 * 12
        for key, val in scored_1.items():
            assert scored_4[key] == val, key
        assert alerts_1 == alerts_4 and alerts_1  # anomalies exist
        assert committed_1 == committed_4 > 0

    run(main())


# -- alert emission off the flush path --------------------------------------

def test_alerts_emitted_off_flush_path_and_counted(run):
    async def main():
        async with egress_runtime(instance_id="eg-al") as rt:
            session = rt.api("rule-processing").engine("acme").session
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            # zscore needs min_history (8) observations per device
            # before it scores; warm with flat values, then one clearly
            # anomalous batch
            for k in range(8):
                await rt.bus.produce(decoded,
                                     _measurements(32, 1000.0 + 60 * k),
                                     key="gw")
            await rt.bus.produce(decoded,
                                 _measurements(32, 2000.0, value=900.0),
                                 key="gw")
            em = rt.api("event-management").management("acme")
            await wait_until(lambda: len(em.spi.alerts) >= 32, timeout=15.0)
            assert rt.metrics.snapshot().get("rules.alerts_emitted",
                                             0) >= 32
            assert session.flights.latency.count >= 32 * 9

    run(main())


def test_alert_path_failure_never_blocks_scoring(run):
    """An alert-store failure is counted and isolated: scoring flushes
    and scored publishes keep flowing (the satellite-1 guarantee)."""
    async def main():
        async with egress_runtime(instance_id="eg-ab") as rt:
            em = rt.api("event-management").management("acme")

            def boom(batch):
                raise RuntimeError("alert store down")

            em.spi.add_alert_batch = boom
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            scored_topic = rt.naming.tenant_topic(
                "acme", TopicNaming.SCORED_EVENTS)
            consumer = rt.bus.subscribe(scored_topic, group="eg-ab-meter")
            # 8 warm batches (zscore min_history), then anomalous ones
            # that force the alert path on every flush
            for k in range(8):
                await rt.bus.produce(decoded,
                                     _measurements(32, 1000.0 + 60 * k),
                                     key="gw")
            for k in range(3):
                await rt.bus.produce(
                    decoded, _measurements(32, 2000.0 + 60 * k,
                                           value=900.0), key="gw")
            seen = 0

            def drained():
                nonlocal seen
                seen += sum(len(r.value) for r in
                            consumer.poll_nowait(max_records=64))
                return seen >= 32 * 11

            await wait_until(drained, timeout=15.0)
            assert rt.metrics.snapshot().get("egress.alert_failures",
                                             0) > 0
            consumer.close()

    run(main())


def test_egress_backlog_is_bounded_and_drains(run):
    """A slow (not failing) publish path surfaces as backpressure —
    `backlogged` through the commit barrier, pausing the consumer —
    never as an unbounded in-memory queue; when the path clears, the
    backlog drains and every batch publishes."""
    async def main():
        async with egress_runtime(instance_id="eg-bp") as rt:
            eng = rt.api("rule-processing").engine("acme")
            egress = eng.egress
            gate = asyncio.Event()
            slow_calls = 0

            async def slow_produce(topic, value, key=None, **kw):
                nonlocal slow_calls
                slow_calls += 1
                await gate.wait()
                return rt.bus.produce_nowait(topic, value, key=key)

            # force the shard path (no sync fast path) onto a publish
            # that stalls until released (instance attribute shadows
            # the method — the shard resolves bus.produce per call)
            egress._produce_nowait = None
            rt.bus.produce = slow_produce
            try:
                cap = egress.MAX_BACKLOG_PER_SHARD * egress.lanes
                for k in range(cap + 8):
                    egress.submit(_scored(eng, 4, 1000.0 + k))
                await asyncio.sleep(0.05)
                assert egress.backlogged
                from sitewhere_tpu.kernel.egresslane import EgressBarrier
                barrier = EgressBarrier(eng.session, egress)
                assert barrier.backlogged  # the consumer-loop pause view
                assert barrier.settled_through == -1  # offsets held
            finally:
                gate.set()
            await egress.drain(timeout=15.0)
            del rt.bus.produce  # restore the real method for teardown
            assert egress.idle and not egress.backlogged
            assert rt.metrics.snapshot().get(
                "egress.publish_failures", 0) == 0

    run(main())


def _scored(eng, n, t):
    from sitewhere_tpu.domain.batch import ScoredBatch
    return ScoredBatch(
        BatchContext(tenant_id="acme", source="gw"),
        np.arange(n, dtype=np.uint32), np.zeros(n, np.float32),
        np.zeros(n, bool), np.full(n, t))


# -- chaos on the egress stage and the sharded loops ------------------------

def test_egress_publish_chaos_quarantine_and_replay(run):
    """`egress.publish` faults: the scored batch is quarantined to the
    tenant DLQ with egress provenance — and a DLQ replay re-produces it
    onto the scored topic (nothing is ever silently dropped)."""
    async def main():
        from sitewhere_tpu.kernel.dlq import (
            list_dead_letters,
            replay_dead_letters,
        )
        from sitewhere_tpu.kernel.faults import FaultInjector
        from sitewhere_tpu.kernel.lifecycle import LifecycleStatus

        fi = FaultInjector(seed=3)
        async with egress_runtime(faults=fi, instance_id="eg-ch") as rt:
            eng = rt.api("rule-processing").engine("acme")
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            dlq = rt.naming.tenant_topic("acme", TopicNaming.DEAD_LETTER)
            scored_topic = rt.naming.tenant_topic(
                "acme", TopicNaming.SCORED_EVENTS)
            fi.arm("egress.publish", rate=1.0, max_faults=1)
            await rt.bus.produce(decoded, _measurements(32, 1000.0),
                                 key="gw")
            await wait_until(
                lambda: len(list_dead_letters(rt.bus, dlq)) >= 1,
                timeout=15.0)
            entries = list_dead_letters(rt.bus, dlq)
            assert len(entries) == 1
            assert "egress" in entries[0][1]["stage"]
            assert entries[0][1]["original_topic"] == scored_topic
            assert isinstance(entries[0][1]["value"].score, np.ndarray)
            snap = rt.metrics.snapshot()
            assert snap.get("egress.publish_failures", 0) == 1
            # the shard survived the injected fault (quarantine, not
            # crash) and later batches publish normally
            assert eng.egress.shards[0].status is LifecycleStatus.STARTED
            consumer = rt.bus.subscribe(scored_topic, group="eg-ch-meter")
            await rt.bus.produce(decoded, _measurements(32, 1060.0),
                                 key="gw")
            seen = []

            def events_seen(at_least):
                def check():
                    seen.extend(consumer.poll_nowait(max_records=64))
                    return sum(len(r.value) for r in seen) >= at_least
                return check

            await wait_until(events_seen(32), timeout=15.0)
            # replay the quarantined batch back onto the scored topic
            n = await replay_dead_letters(rt.bus, dlq,
                                          metrics=rt.metrics)
            assert n == 1
            await wait_until(events_seen(64), timeout=15.0)
            consumer.close()

    run(main())


def test_sharded_loops_survive_crash_faults(run):
    """Crash faults on the sharded consumer loops: the supervisor
    restarts them (restart counters move), no shard wedges, and the
    full sequence still scores and publishes exactly once per
    delivery."""
    async def main():
        from sitewhere_tpu.kernel.faults import FaultInjector
        from sitewhere_tpu.kernel.lifecycle import LifecycleStatus

        fi = FaultInjector(seed=7)
        async with egress_runtime(egress={"lanes": 4}, faults=fi,
                                  instance_id="eg-sv") as rt:
            eng = rt.api("rule-processing").engine("acme")
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            scored_topic = rt.naming.tenant_topic(
                "acme", TopicNaming.SCORED_EVENTS)
            consumer = rt.bus.subscribe(scored_topic, group="eg-sv-meter")
            fi.arm("bus.poll", rate=0.05, max_faults=6)
            for k in range(12):
                await rt.bus.produce(decoded,
                                     _measurements(32, 1000.0 + 60 * k),
                                     key=f"gw{k}")
            seen = 0

            def drained():
                nonlocal seen
                from sitewhere_tpu.kernel.faults import FaultInjected
                try:
                    records = consumer.poll_nowait(max_records=128)
                except FaultInjected:
                    return False  # the armed site hit OUR meter poll
                seen += sum(len(r.value) for r in records)
                return seen >= 12 * 32

            await wait_until(drained, timeout=30.0)
            fi.disarm()
            restarts = rt.metrics.counter("supervisor.restarts").value
            assert restarts > 0  # crashes happened and were healed
            await wait_until(lambda: all(
                lane.status is LifecycleStatus.STARTED
                for lane in eng.fastlanes), timeout=15.0)
            assert all(sh.status is LifecycleStatus.STARTED
                       for sh in eng.egress.shards)
            consumer.close()

    run(main())
