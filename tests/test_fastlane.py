"""Fused ingress fast lane (kernel/fastlane.py): lane selection, lane
equivalence against the staged slow lane, and the platform contracts
(DLQ quarantine, flow-control shed routing, chaos site) on the fused
path — ISSUE 5's acceptance tests.

Equivalence is behavioral: the SAME event sequence driven through a
fastlane-on and a fastlane-off runtime must produce identical scored
outputs, identical persisted telemetry, and identical
unregistered-device splits."""

import asyncio
import contextlib

import numpy as np

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.domain.model import DeviceType
from sitewhere_tpu.kernel.bus import TopicNaming
from sitewhere_tpu.kernel.fastlane import fastlane_enabled
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
async def lane_runtime(num_devices=32, fastlane=None, faults=None,
                       instance_id="lane"):
    """Full pipeline runtime with tenant 'acme'; `fastlane` pins the
    lane via the tenant override (None = auto-detection)."""
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
    await rt.add_tenant(TenantConfig(tenant_id="acme", sections=sections))
    dm = rt.api("device-management").management("acme")
    dm.bootstrap_fleet(DeviceType(token="thermo", name="T"), num_devices)
    session = rt.api("rule-processing").engine("acme").session
    await wait_until(lambda: session.ready, timeout=60.0)
    try:
        yield rt
    finally:
        await rt.stop()


def _measurements(n: int, t: float, start: int = 0) -> MeasurementBatch:
    return MeasurementBatch(
        BatchContext(tenant_id="acme", source="test"),
        np.arange(start, start + n, dtype=np.uint32),
        np.zeros(n, np.uint16), np.full(n, 21.0, np.float32),
        np.full(n, t))


# -- lane selection ---------------------------------------------------------

def test_lane_selection_and_wiring(run):
    async def main():
        # auto-detected ON: rule engine hosts the FastLane, inbound
        # engine does NOT spin its staged consumer
        async with lane_runtime() as rt:
            assert rt.api("rule-processing").engine("acme").fastlane \
                is not None
            assert rt.services["inbound-processing"] \
                .engines["acme"].processor is None
            # predicate declines config-declared custom rules (the
            # fully staged lane keeps their ordering story)
            scripted = TenantConfig(tenant_id="s", sections={
                "rule-processing": {"model": "zscore",
                                    "scripts": {"x": "pass"}}})
            assert not fastlane_enabled(scripted, rt)
            fenced = TenantConfig(tenant_id="f", sections={
                "rule-processing": {"model": "zscore",
                                    "geofences": [{"n": 1}]}})
            assert not fastlane_enabled(fenced, rt)
            # ... and scoring-disabled tenants (nothing to fuse toward)
            no_model = TenantConfig(tenant_id="n", sections={
                "rule-processing": {"model": None}})
            assert not fastlane_enabled(no_model, rt)
            # explicit override beats auto-detection either way
            forced_on = TenantConfig(tenant_id="o", sections={
                "fastlane": {"enabled": True},
                "rule-processing": {"model": "zscore",
                                    "scripts": {"x": "pass"}}})
            assert fastlane_enabled(forced_on, rt)
        # pinned OFF: staged lane wired exactly as before
        async with lane_runtime(fastlane=False, instance_id="lane2") as rt:
            assert rt.api("rule-processing").engine("acme").fastlane is None
            assert rt.services["inbound-processing"] \
                .engines["acme"].processor is not None

    run(main())


# -- lane equivalence -------------------------------------------------------

async def _drive_and_collect(rt, n_sim=48, ticks=6):
    """Feed `ticks` simulator payloads via the default receiver and
    return (scored {(device, ts) -> (score, is_anomaly)}, telemetry
    total, unregistered-record count)."""
    scored_topic = rt.naming.tenant_topic("acme", TopicNaming.SCORED_EVENTS)
    consumer = rt.bus.subscribe(scored_topic, group="lane-test-meter")
    sim = DeviceSimulator(SimConfig(num_devices=n_sim, seed=7),
                          tenant_id="acme")
    receiver = rt.api("event-sources").engine("acme").receiver("default")
    for k in range(ticks):
        payload, _ = sim.payload(t=1000.0 + 60.0 * k)
        assert await receiver.submit(payload)
    session = rt.api("rule-processing").engine("acme").session
    expected = 32 * ticks  # only the registered 32 of n_sim are scored
    await wait_until(lambda: session.flights.latency.count >= expected,
                     timeout=30.0)
    em = rt.api("event-management").management("acme")
    await wait_until(lambda: em.telemetry.total_events >= expected,
                     timeout=30.0)
    # collect off the TOPIC, waiting on published records: with the
    # fused egress stage (kernel/egresslane.py) a settled flush is
    # published a beat later by the shard loop, so settle count alone
    # no longer implies the records are poll-able
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
    unreg_topic = rt.naming.tenant_topic(
        "acme", TopicNaming.UNREGISTERED_DEVICES)
    unreg = sum(len(r.value["device_indices"])
                for r in rt.bus.peek(unreg_topic, limit=-1)
                if isinstance(r.value, dict))
    return scored, em.telemetry.total_events, unreg


def test_lane_equivalence_scored_outputs_and_splits(run):
    """Same event sequence, both lanes: identical per-event scores,
    identical persisted telemetry, identical unregistered splits."""
    async def main():
        async with lane_runtime(fastlane=True, instance_id="on") as rt_on:
            fast = await _drive_and_collect(rt_on)
            # the fused lane did the validation: its counters moved
            assert rt_on.metrics.meter(
                "fastlane.events_processed").rate(60.0) > 0
            assert rt_on.metrics.counter(
                "fastlane.events_unregistered").value == 16 * 6
        async with lane_runtime(fastlane=False, instance_id="off") as rt_off:
            slow = await _drive_and_collect(rt_off)
        scored_f, total_f, unreg_f = fast
        scored_s, total_s, unreg_s = slow
        assert total_f == total_s == 32 * 6
        assert unreg_f == unreg_s == 16 * 6
        assert scored_f.keys() == scored_s.keys()
        assert len(scored_f) == 32 * 6
        for key, (score, anom) in scored_f.items():
            assert scored_s[key] == (score, anom), key

    run(main())


def test_fastlane_batches_not_rescored_at_enriched_hop(run):
    """The ctx.fastlane flag stops the rule processor re-admitting what
    the fused loop already scored — exactly-once scoring per delivery."""
    async def main():
        async with lane_runtime() as rt:
            session = rt.api("rule-processing").engine("acme").session
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            await rt.bus.produce(decoded, _measurements(32, 1000.0),
                                 key="gw")
            await wait_until(lambda: session.flights.latency.count >= 32)
            # the enriched hop has long since seen the batch; give any
            # (wrong) second admission time to surface
            em = rt.api("event-management").management("acme")
            await wait_until(lambda: em.telemetry.total_events >= 32)
            await asyncio.sleep(0.3)
            assert session.flights.latency.count == 32

    run(main())


def test_stale_fastlane_flag_cleared_by_staged_lane(run):
    """A record the fused lane handled mutates the shared ctx in the
    decoded-topic log; if it redelivers into the STAGED lane (lane
    toggle with uncommitted offsets), the stale flag must not make the
    rule processor skip scoring it — the staged lane reclaims the
    batch."""
    async def main():
        async with lane_runtime(fastlane=False, instance_id="stale") as rt:
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            batch = _measurements(32, 1000.0)
            batch.ctx.fastlane = True  # as a pre-toggle fused pass left it
            await rt.bus.produce(decoded, batch, key="gw")
            session = rt.api("rule-processing").engine("acme").session
            await wait_until(lambda: session.flights.latency.count >= 32)

    run(main())


# -- contracts on the fused path --------------------------------------------

def test_fastlane_poison_record_quarantined(run):
    """DLQ01 behaviorally: a poison decoded record lands in the tenant
    DLQ with fastlane provenance and the lane keeps flowing."""
    async def main():
        from sitewhere_tpu.kernel.dlq import list_dead_letters

        async with lane_runtime() as rt:
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            dlq = rt.naming.tenant_topic("acme", TopicNaming.DEAD_LETTER)
            poison = _measurements(8, 1000.0)
            # string device indices break the registration-mask gather
            poison.device_index = np.array(["x"] * 8, dtype=object)
            await rt.bus.produce(decoded, poison, key="gw")
            await rt.bus.produce(decoded, _measurements(32, 1001.0),
                                 key="gw")
            session = rt.api("rule-processing").engine("acme").session
            await wait_until(lambda: session.flights.latency.count >= 32)
            entries = list_dead_letters(rt.bus, dlq)
            assert len(entries) == 1
            assert "fastlane" in entries[0][1]["stage"]
            assert entries[0][1]["original_topic"] == decoded

    run(main())


def test_fastlane_chaos_site_armed(run):
    """`fastlane.handle` is a registered chaos site: injected faults
    quarantine exactly the faulted records, the loop survives."""
    async def main():
        from sitewhere_tpu.kernel.dlq import list_dead_letters
        from sitewhere_tpu.kernel.faults import FaultInjector
        from sitewhere_tpu.kernel.lifecycle import LifecycleStatus

        fi = FaultInjector(seed=11)
        async with lane_runtime(faults=fi) as rt:
            fi.arm("fastlane.handle", rate=1.0, max_faults=2)
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            dlq = rt.naming.tenant_topic("acme", TopicNaming.DEAD_LETTER)
            for k in range(4):
                await rt.bus.produce(decoded, _measurements(32, 1000.0 + k),
                                     key="gw")
            session = rt.api("rule-processing").engine("acme").session
            # 2 records quarantined, the other 2 score through
            await wait_until(lambda: session.flights.latency.count >= 64)
            await wait_until(
                lambda: len(list_dead_letters(rt.bus, dlq)) == 2)
            lane = rt.api("rule-processing").engine("acme").fastlane
            assert lane.status is LifecycleStatus.STARTED

    run(main())


def test_fastlane_shed_defer_and_degrade(run):
    """Flow-control routing on the fused path mirrors the slow lane:
    defer spools to the deferred topic (drained back when pressure
    clears), degrade scores via the host fallback (model_version -1)."""
    async def main():
        async with lane_runtime() as rt:
            session = rt.api("rule-processing").engine("acme").session
            decoded = rt.naming.tenant_topic(
                "acme", TopicNaming.EVENT_SOURCE_DECODED)
            deferred = rt.naming.tenant_topic(
                "acme", TopicNaming.DEFERRED_EVENTS)
            scored_topic = rt.naming.tenant_topic(
                "acme", TopicNaming.SCORED_EVENTS)
            em = rt.api("event-management").management("acme")

            rt.flow.force_mode("acme", "defer")
            await rt.bus.produce(decoded, _measurements(32, 1000.0),
                                 key="gw")
            await wait_until(lambda: sum(
                len(r.value) for r in rt.bus.peek(deferred, limit=-1)) >= 32)
            # spooled, persisted, NOT scored
            await wait_until(lambda: em.telemetry.total_events >= 32)
            assert session.flights.latency.count == 0
            assert rt.metrics.snapshot().get("flow.shed_defer:acme", 0) >= 32

            # pressure clears → the rule processor drains the spool back
            rt.flow.force_mode("acme", "ok")
            await wait_until(lambda: session.flights.latency.count >= 32,
                             timeout=15.0)
            assert rt.metrics.snapshot().get(
                "flow.deferred_replayed:acme", 0) >= 32

            # degrade: host-side fallback, no XLA dispatch
            consumer = rt.bus.subscribe(scored_topic, group="lane-deg")
            rt.flow.force_mode("acme", "degrade")
            await rt.bus.produce(decoded, _measurements(32, 2000.0),
                                 key="gw")
            scored = []

            def got_fallback():
                scored.extend(r.value
                              for r in consumer.poll_nowait(max_records=64))
                return any(b.model_version == -1 for b in scored)

            await wait_until(got_fallback)
            assert rt.metrics.snapshot().get(
                "flow.shed_degrade:acme", 0) >= 32
            consumer.close()

    run(main())


# -- scoring-server coalescing (tentpole rider) ------------------------------

def test_sub_bucket_admits_coalesce(run):
    """N sub-bucket admits inside one batch window dispatch as ONE
    flush — the window, not the admit count, drives dispatch."""
    async def main():
        from sitewhere_tpu.kernel.metrics import MetricsRegistry
        from sitewhere_tpu.models import build_model
        from sitewhere_tpu.persistence.telemetry import TelemetryStore
        from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession

        session = ScoringSession(
            build_model("zscore", window=16), TelemetryStore(history=32),
            MetricsRegistry(),
            ScoringConfig(buckets=(256,), batch_window_ms=50.0))
        session.warmup()
        for k in range(5):
            session.admit(_measurements(8, 1000.0 + k, start=8 * k))
            assert not session.flush_due  # window still open, sub-bucket
        assert session.pending_n == 40
        await asyncio.sleep(0.06)  # window closes
        assert session.flush_due
        assert session.flush_nowait()
        assert session.dispatch_count == 1  # ONE dispatch for 5 admits
        await session.drain()

    run(main())


def test_single_admit_flush_is_zero_copy(run):
    """The saturation steady state (one fleet-sized admit per window)
    must not memcpy the columns through `_take_pending`."""
    async def main():
        from sitewhere_tpu.kernel.metrics import MetricsRegistry
        from sitewhere_tpu.models import build_model
        from sitewhere_tpu.persistence.telemetry import TelemetryStore
        from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession

        session = ScoringSession(
            build_model("zscore", window=16), TelemetryStore(history=32),
            MetricsRegistry(), ScoringConfig(buckets=(256,)))
        batch = _measurements(64, 1000.0)
        session.admit(batch)
        dev, val, ts, ingest, ctx, traces = session._take_pending()
        assert dev is batch.device_index  # the view, not a concat copy
        assert val is batch.value
        assert ts is batch.ts
        assert ctx is batch.ctx
        assert [(t[0], t[1]) for t in traces] == [(ctx.trace_id, 64)]
        assert session.pending_n == 0

    run(main())


# -- decoder satellite -------------------------------------------------------

def test_requests_to_batches_single_pass_equivalence():
    """The vectorized one-pass column build preserves the decoder
    contract: known tokens → columnar batches, unknown tokens →
    auto-registration, explicit registrations pass through."""
    from sitewhere_tpu.domain.batch import (
        LocationBatch,
        RegistrationBatch,
    )
    from sitewhere_tpu.services.event_sources import requests_to_batches

    ctx = BatchContext(tenant_id="t", source="s")
    table = {"a": 0, "b": 3, "c": 7}

    def resolve(tokens):
        return [table.get(t, -1) for t in tokens]

    reqs = [
        {"type": "measurement", "device": "a", "value": 1.5, "ts": 10.0},
        {"type": "measurement", "device": "ghost", "value": 2.0},
        {"type": "measurement", "device": "b", "mtype": 2, "value": 2.5,
         "ts": 11.0},
        {"type": "location", "device": "c", "lat": 33.7, "lon": -84.4,
         "ts": 12.0},
        {"type": "location", "device": "spook", "lat": 1.0, "lon": 2.0},
        {"type": "registration", "device": "new", "deviceType": "tt"},
    ]
    out = requests_to_batches(reqs, ctx, resolve)
    regs = [b for b in out if isinstance(b, RegistrationBatch)]
    meas = [b for b in out if isinstance(b, MeasurementBatch)]
    locs = [b for b in out if isinstance(b, LocationBatch)]
    assert len(meas) == 1 and len(locs) == 1 and len(regs) == 3
    assert {t for r in regs for t in r.device_tokens} == \
        {"new", "ghost", "spook"}
    m = meas[0]
    np.testing.assert_array_equal(m.device_index, [0, 3])
    np.testing.assert_array_equal(m.mtype, [0, 2])
    np.testing.assert_allclose(m.value, [1.5, 2.5])
    np.testing.assert_allclose(m.ts, [10.0, 11.0])
    loc = locs[0]
    np.testing.assert_array_equal(loc.device_index, [7])
    np.testing.assert_allclose(loc.latitude, [33.7])
    np.testing.assert_allclose(loc.longitude, [-84.4])
    np.testing.assert_allclose(loc.ts, [12.0])


def test_requests_to_batches_ignores_fields_of_unknown_devices():
    """A malformed optional field on an UNREGISTERED device's row must
    not poison the registered rows: that row only becomes a
    registration request, its value/ts are never read (regression for
    the single-pass column build)."""
    from sitewhere_tpu.domain.batch import RegistrationBatch
    from sitewhere_tpu.services.event_sources import requests_to_batches

    ctx = BatchContext(tenant_id="t", source="s")

    def resolve(tokens):
        return [{"a": 0}.get(t, -1) for t in tokens]

    reqs = [
        {"type": "measurement", "device": "a", "value": 1.5, "ts": 10.0},
        {"type": "measurement", "device": "ghost", "value": "not-a-float",
         "ts": None},
        {"type": "location", "device": "spook", "lat": "garbage"},
    ]
    out = requests_to_batches(reqs, ctx, resolve)
    meas = [b for b in out if isinstance(b, MeasurementBatch)]
    regs = [b for b in out if isinstance(b, RegistrationBatch)]
    assert len(meas) == 1 and len(regs) == 2
    np.testing.assert_allclose(meas[0].value, [1.5])
    assert {t for r in regs for t in r.device_tokens} == {"ghost", "spook"}
