"""End-to-end pipeline test: config 1 [BASELINE.json configs[0]].

simulator → event-sources(SWB1 decode) → inbound-processing(mask check) →
event-management(columnar persist) → device-state(merge), single tenant
[SURVEY.md §3.2, §7 step 2].
"""

import asyncio
import contextlib

import numpy as np

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.domain.model import DeviceType
from sitewhere_tpu.kernel.service import ServiceRuntime
from sitewhere_tpu.services import (
    DeviceManagementService,
    DeviceStateService,
    EventManagementService,
    EventSourcesService,
    InboundProcessingService,
)
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig


@contextlib.asynccontextmanager
async def running_pipeline(num_devices: int = 100, sections: dict | None = None,
                           extra_services: tuple = ()):
    """Started runtime with tenant 'acme' and a registered fleet."""
    from sitewhere_tpu.services import RuleProcessingService

    rt = ServiceRuntime(InstanceSettings(instance_id="e2e"))
    rt.add_service(DeviceManagementService(rt))
    rt.add_service(EventSourcesService(rt))
    rt.add_service(InboundProcessingService(rt))
    rt.add_service(EventManagementService(rt))
    rt.add_service(DeviceStateService(rt))
    if sections and "rule-processing" in sections:
        rt.add_service(RuleProcessingService(rt))
    for cls in extra_services:
        rt.add_service(cls(rt))
    await rt.start()
    await rt.add_tenant(TenantConfig(tenant_id="acme", sections=sections or {}))
    dm = rt.api("device-management").management("acme")
    dt = DeviceType(token="thermo", name="Thermometer", channels=("temp",))
    dm.bootstrap_fleet(dt, num_devices)
    try:
        yield rt
    finally:
        await rt.stop()


async def wait_until(predicate, timeout=10.0, interval=0.02):
    deadline = asyncio.get_event_loop().time() + timeout
    while True:
        if predicate():
            return
        if asyncio.get_event_loop().time() > deadline:
            raise TimeoutError("condition not met")
        await asyncio.sleep(interval)


def test_e2e_swb1_ingest_to_state(run):
    async def main():
        async with running_pipeline() as rt:
            sim = DeviceSimulator(SimConfig(num_devices=100), tenant_id="acme")
            receiver = rt.api("event-sources").engine("acme").receiver("default")
            for k in range(5):
                payload, _ = sim.payload(t=1000.0 + k)
                await receiver.submit(payload)

            em = rt.api("event-management").management("acme")
            await wait_until(lambda: em.telemetry.total_events == 500)

            # persisted history is chronological per device
            table = em.telemetry.channel(0)
            vals, valid = table.window(np.arange(100), 5)
            assert valid.all()
            tss = table.window_ts(np.arange(100), 5)
            np.testing.assert_array_equal(
                tss[0], [1000., 1001., 1002., 1003., 1004.])

            # device-state materialized the newest reading
            state_engine = rt.api("device-state").state("acme")
            await wait_until(
                lambda: state_engine.last_seen[:100].min() == 1004.0)
            st = state_engine.get_state(42)
            assert st["last_seen"] == 1004.0
            assert st["channels"][0]["ts"] == 1004.0
            np.testing.assert_allclose(st["channels"][0]["value"],
                                       vals[42, -1], rtol=1e-6)

    run(main())


def test_unregistered_devices_split_off(run):
    async def main():
        async with running_pipeline(num_devices=100) as rt:
            # simulate 150 devices but only 100 are registered
            sim = DeviceSimulator(SimConfig(num_devices=150), tenant_id="acme")
            receiver = rt.api("event-sources").engine("acme").receiver("default")
            payload, _ = sim.payload(t=2000.0)
            await receiver.submit(payload)

            em = rt.api("event-management").management("acme")
            await wait_until(lambda: em.telemetry.total_events == 100)
            await asyncio.sleep(0.05)
            assert em.telemetry.total_events == 100  # unknown 50 never persist

            topic = rt.naming.tenant_topic("acme", "unregistered-device-events")
            assert sum(rt.bus.end_offsets(topic)) == 1

    run(main())


def test_json_decoder_and_failed_decode(run):
    async def main():
        async with running_pipeline() as rt:
            sources = rt.api("event-sources").engine("acme")
            sources.add_receiver(
                {"kind": "queue", "decoder": "json", "name": "json-in"})
            await sources.receiver("json-in").start()

            payload = (
                b'{"requests": ['
                b'{"type": "measurement", "device": "dev-7", "value": 33.5,'
                b' "ts": 3000},'
                b'{"type": "measurement", "device": "ghost", "value": 1.0},'
                b'{"type": "location", "device": "dev-8", "lat": 33.7,'
                b' "lon": -84.4}]}')
            await sources.receiver("json-in").submit(payload)

            em = rt.api("event-management").management("acme")
            # the location is persisted on a hop of its own, after the
            # measurement may already count: wait for both
            await wait_until(lambda: em.telemetry.total_events >= 1
                             and em.list_locations(8))
            ms = em.list_measurements(7)
            assert [m.value for m in ms] == [33.5]
            locs = em.list_locations(8)
            assert len(locs) == 1 and abs(locs[0].latitude - 33.7) < 1e-9

            # garbage payload → failed-decode topic, pipeline stays up
            await sources.receiver("json-in").submit(b"\x00garbage")
            failed = rt.naming.tenant_topic(
                "acme", "event-source-failed-decode-events")
            await wait_until(lambda: sum(rt.bus.end_offsets(failed)) == 1)

    run(main())


def test_tcp_receiver_roundtrip(run):
    async def main():
        async with running_pipeline(num_devices=10) as rt:
            sim = DeviceSimulator(SimConfig(num_devices=10), tenant_id="acme")
            sources = rt.api("event-sources").engine("acme")
            tcp = sources.add_receiver(
                {"kind": "tcp", "decoder": "swb1", "name": "tcp-in"})
            await tcp.start()
            payload, _ = sim.payload(t=4000.0)
            reader, writer = await asyncio.open_connection("127.0.0.1", tcp.port)
            writer.write(len(payload).to_bytes(4, "little") + payload)
            await writer.drain()
            em = rt.api("event-management").management("acme")
            await wait_until(lambda: em.telemetry.total_events >= 10)
            writer.close()

    run(main())


def test_simulator_anomaly_injection():
    sim = DeviceSimulator(SimConfig(num_devices=5000, anomaly_rate=0.02,
                                    anomaly_magnitude=10.0), tenant_id="t")
    batch, truth = sim.tick(t=0.0)
    assert 0.005 < truth.mean() < 0.06
    # anomalous readings are far from their own device's baseline
    # (amplitude ≤ 3, noise σ=0.15, injected magnitude 10)
    own_base = sim.base[batch.device_index.astype(np.int64)]
    deviation = np.abs(batch.value - own_base)
    assert deviation[truth].min() > 5.0
    assert deviation[~truth].max() < 5.0


def test_pipeline_spans_recorded(run):
    """§5.1: sampled traces leave one span per pipeline stage, queryable
    by trace id (decode → enrich → persist → score)."""

    async def main():
        from tests.test_pipeline import running_pipeline, wait_until
        sections = {"rule-processing": {"model": "zscore",
                                        "model_config": {"window": 16},
                                        "batch_window_ms": 1.0}}
        async with running_pipeline(num_devices=20, sections=sections) as rt:
            rt.tracer.sample = 1  # record every trace for the test
            from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig
            sim = DeviceSimulator(SimConfig(num_devices=20), tenant_id="acme")
            receiver = rt.api("event-sources").engine("acme").receiver("default")
            session = rt.api("rule-processing").engine("acme").session
            for k in range(20):
                await receiver.submit(sim.payload(t=60.0 * k)[0])
            await wait_until(lambda: session.flights.latency.count >= 400)
            summary = rt.tracer.stage_summary()
            for stage in ("event-sources.decode", "inbound.enrich",
                          "event-management.persist", "rule-processing.score"):
                assert stage in summary, (stage, summary.keys())
                assert summary[stage]["events"] > 0
            # one trace's journey is ordered receive → decode → ... → score
            scored = [s for s in rt.tracer.spans("rule-processing.score")
                      if s.n_events > 0]
            journey = rt.tracer.trace(scored[0].trace_id)
            stages = [s.stage for s in journey]
            assert stages.index("event-sources.receive") == 0
            assert stages.index("event-sources.decode") == 1
            assert "event-management.persist" in stages

    run(main())
