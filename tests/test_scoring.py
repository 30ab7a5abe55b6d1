"""Scoring server + rule-processing hook tests (config 2 [BASELINE.json]):
simulator → pipeline → XLA-scored anomaly alerts [SURVEY.md §7 step 3]."""

import asyncio

import numpy as np

from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

from tests.test_pipeline import running_pipeline, wait_until


def _fill_store(store: TelemetryStore, sim: DeviceSimulator, ticks: int,
                t0: float = 0.0):
    for k in range(ticks):
        batch, _ = sim.tick(t=t0 + 60.0 * k)
        store.append_measurements(batch)


def test_scoring_session_detects_injected_anomalies(run):
    async def main():
        store = TelemetryStore(history=128)
        sim = DeviceSimulator(SimConfig(num_devices=200, seed=3), tenant_id="t")
        _fill_store(store, sim, 70)  # warm history, no anomalies

        session = ScoringSession(
            build_model("zscore", window=64), store, MetricsRegistry(),
            ScoringConfig(buckets=(256,), threshold=4.0))
        session.warmup()

        # final tick with injected anomalies lands in the store
        sim.cfg = SimConfig(num_devices=200, seed=3, anomaly_rate=0.05,
                            anomaly_magnitude=12.0)
        batch, truth = sim.tick(t=70 * 60.0)
        store.append_measurements(batch)

        scored = await session.score_devices(
            batch.device_index, batch.ts,
            np.zeros(len(batch)), batch.ctx)
        detected = scored.is_anomaly
        # perfect separation for 12-sigma-ish spikes vs zscore rule
        assert (detected == truth).mean() > 0.97
        assert detected[truth].mean() > 0.9

    run(main())


def test_scoring_bucket_padding_and_chunking(run):
    async def main():
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=600), tenant_id="t")
        _fill_store(store, sim, 40)
        session = ScoringSession(
            build_model("zscore", window=32), store, MetricsRegistry(),
            ScoringConfig(buckets=(64, 256), threshold=4.0))
        # 600 devices with max bucket 256 → chunks of 256/256/88→pad 256
        devices = np.arange(600, dtype=np.uint32)
        scored = await session.score_devices(
            devices, np.zeros(600), np.zeros(600),
            BatchContext(tenant_id="t"))
        assert len(scored) == 600
        assert np.isfinite(scored.score).all()

    run(main())


def test_admission_batching_deadline(run):
    async def main():
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=10), tenant_id="t")
        _fill_store(store, sim, 40)
        session = ScoringSession(
            build_model("zscore", window=32), store, MetricsRegistry(),
            ScoringConfig(buckets=(64,), batch_window_ms=5.0))
        batch, _ = sim.tick(t=41 * 60.0)
        assert not session.flush_due
        session.admit(batch)
        assert not session.flush_due  # deadline not reached
        await asyncio.sleep(0.006)
        assert session.flush_due
        scored = await session.flush()
        assert len(scored) == 10
        assert session.flush_due is False and await session.flush() is None

    run(main())


def test_e2e_scoring_alerts_in_pipeline(run):
    """Full config-2 slice: ingest → persist → score → model alerts."""

    async def main():
        sections = {"rule-processing": {"model": "zscore",
                                        "model_config": {"window": 32},
                                        "threshold": 5.0,
                                        "batch_window_ms": 1.0}}
        async with running_pipeline(num_devices=100,
                                    sections=sections) as rt:
            sim = DeviceSimulator(SimConfig(num_devices=100, seed=11),
                                  tenant_id="acme")
            receiver = rt.api("event-sources").engine("acme").receiver("default")
            # history: clean
            for k in range(40):
                payload, _ = sim.payload(t=60.0 * k)
                await receiver.submit(payload)
            em = rt.api("event-management").management("acme")
            await wait_until(lambda: em.telemetry.total_events == 4000)
            # let scoring drain history before the anomaly tick: otherwise
            # a history row flushed together with the anomaly shares its
            # post-anomaly window and yields extra (correct-but-untracked)
            # alerts for the same devices
            session = rt.api("rule-processing").engine("acme").session
            await wait_until(lambda: session.flights.latency.count >= 4000,
                             timeout=30.0)

            # anomaly tick
            sim.cfg = SimConfig(num_devices=100, seed=11, anomaly_rate=0.1,
                                anomaly_magnitude=15.0)
            payload, truth = sim.payload(t=41 * 60.0)
            await receiver.submit(payload)

            n_true = int(truth.sum())
            assert n_true > 0
            # scope the strict device check to the anomaly tick: early
            # partial windows (cold start) may produce borderline alerts
            # on clean data, which is the zscore rule working as designed
            anom_ts = 41 * 60.0

            def tick_alerts():
                return [a for a in em.list_alerts() if a.event_date == anom_ts]

            await wait_until(lambda: len(tick_alerts()) >= n_true,
                             timeout=15.0)
            alerts = tick_alerts()
            assert all(a.source == "model" for a in alerts)
            assert all(a.type == "anomaly.zscore" for a in alerts)
            # alerts point at exactly the truly anomalous devices
            dm = rt.api("device-management").management("acme")
            alert_devices = {dm.get_device(a.device_id).index for a in alerts}
            true_devices = set(np.nonzero(truth)[0].tolist())
            assert alert_devices == true_devices

            # scored batches were published for observability
            scored_topic = rt.naming.tenant_topic("acme", "scored-events")
            assert sum(rt.bus.end_offsets(scored_topic)) > 0

            snap = rt.metrics.snapshot()
            assert snap["scoring.events_scored"]["rate_60s"] > 0
            assert snap["scoring.e2e_latency_s"]["count"] >= 4100

    run(main())


def test_python_hook_receives_batches(run):
    """The Groovy-stream-processor capability: python hooks over enriched
    records with api bindings."""

    async def main():
        # model: None → hooks only, no scoring session
        async with running_pipeline(
                num_devices=10,
                sections={"rule-processing": {"model": None}}) as rt:
            engine = rt.api("rule-processing").engine("acme")
            seen = []

            async def hook(value, api):
                if isinstance(value, MeasurementBatch):
                    seen.append(len(value))
                    if len(seen) == 1:
                        await api.emit_alert(3, 1, "custom", "hook fired")

            engine.add_hook("test-hook", hook)
            sim = DeviceSimulator(SimConfig(num_devices=10), tenant_id="acme")
            receiver = rt.api("event-sources").engine("acme").receiver("default")
            await receiver.submit(sim.payload(t=100.0)[0])

            em = rt.api("event-management").management("acme")
            await wait_until(lambda: sum(seen) >= 10)
            await wait_until(
                lambda: any(a.type == "custom" for a in em.list_alerts()))

    run(main())


def test_flush_chunks_fleets_larger_than_max_bucket(run):
    """A flush with more unique devices than the largest bucket must chunk
    (sequentially, preserving order), not crash or drop events."""

    async def main():
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=300), tenant_id="t")
        _fill_store(store, sim, 40)
        delivered = []

        async def sink(batch):
            delivered.append(batch)

        session = ScoringSession(
            build_model("zscore", window=32), store, MetricsRegistry(),
            ScoringConfig(buckets=(128,), batch_window_ms=0.0), sink=sink)
        session.warmup()
        batch, _ = sim.tick(t=41 * 60.0)  # 300 devices > bucket 128
        session.admit(batch)
        scored = await session.flush()
        assert len(scored) == 300
        assert np.isfinite(scored.score).all()
        await session.drain()
        assert session.inflight == 0
        assert sum(len(b) for b in delivered) == 300

    run(main())


def test_ring_duplicate_devices_in_one_flush(run):
    """Several events for one device in a single flush apply in arrival
    order; every event gets the device's newest-window score."""

    async def main():
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=8), tenant_id="t")
        _fill_store(store, sim, 40)
        session = ScoringSession(
            build_model("zscore", window=16), store, MetricsRegistry(),
            ScoringConfig(buckets=(32,), batch_window_ms=0.0, threshold=4.0))
        session.warmup()
        ctx = BatchContext(tenant_id="t", source="test")
        # clean values = each device's own recent level; the final
        # device-3 value is a huge spike
        c3 = float(store.window(np.array([3]), 1)[0][0, 0])
        c5 = float(store.window(np.array([5]), 1)[0][0, 0])
        # device 3 appears 3 times (last value is a huge spike), device 5 once
        batch = MeasurementBatch(
            ctx,
            device_index=np.array([3, 5, 3, 3], np.uint32),
            mtype=np.zeros(4, np.uint16),
            value=np.array([c3, c5, c3, 500.0], np.float32),
            ts=np.full(4, 41 * 60.0))
        session.admit(batch)
        scored = await session.flush()
        assert len(scored) == 4
        # per-occurrence semantics: each event scores against the window
        # as of that event — the two clean 20.0 values score low, the
        # final 500.0 spike scores high (same as per-tick flushes)
        d3 = scored.score[scored.device_index == 3]
        assert d3[0] < 4.0 and d3[1] < 4.0 and d3[2] > 4.0
        assert scored.score[scored.device_index == 5][0] < 4.0
        # ring state: device 3's newest ring entries include the spike
        x, valid = session.ring.windows(np.array([3]))
        assert float(np.asarray(x)[0, -1]) == 500.0
        # in-order: the two pre-spike values precede it chronologically
        got = np.asarray(x)[0, -3:]
        np.testing.assert_allclose(got, [c3, c3, 500.0], rtol=1e-6)

    run(main())


def test_ring_matches_host_store_windows(run):
    """The device-resident ring mirrors the host store when events flow
    through admit/flush (consistency of the two copies)."""

    async def main():
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=50), tenant_id="t")
        _fill_store(store, sim, 20)
        session = ScoringSession(
            build_model("zscore", window=16), store, MetricsRegistry(),
            ScoringConfig(buckets=(64,), batch_window_ms=0.0))
        session.warmup()  # ring seeded from store
        for k in range(21, 25):
            batch, _ = sim.tick(t=60.0 * k)
            store.append_measurements(batch)
            session.admit(batch)
            await session.flush()
        devices = np.arange(50, dtype=np.uint32)
        want_x, want_v = store.window(devices, 16)
        got_x = np.asarray(session.ring.windows(devices)[0])
        got_v = np.asarray(session.ring.windows(devices)[1])
        np.testing.assert_allclose(got_x[want_v], want_x[want_v], rtol=1e-6)
        assert (got_v == want_v).all()

    run(main())


def test_admission_backpressure_never_drops(run):
    """ADVICE regression: an at-capacity admission backlog (e.g. during a
    warmup compile) must NOT drop already-consumed events — the session
    reports `backlogged` and the consumer stops polling instead."""

    async def main():
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=100, seed=1), tenant_id="t")
        _fill_store(store, sim, 40)
        session = ScoringSession(
            build_model("zscore", window=32), store, MetricsRegistry(),
            ScoringConfig(buckets=(128,), threshold=4.0))
        session.ready = False  # simulate a long warmup/regrow
        total = 0
        for k in range(30):  # 30 * 100 = 3000 > default cap 4*128 = 512
            batch, _ = sim.tick(t=(40 + k) * 60.0)
            session.admit(batch)
            total += len(batch)
        assert session.pending_n == total  # nothing dropped
        assert session.backlogged
        # once ready, the backlog drains completely
        session.warmup()
        scored: list = []

        async def sink(b):
            scored.append(len(b))

        session.sink = sink
        while session.pending_n:
            session.flush_nowait()
            await asyncio.sleep(0.01)
        await session.drain()
        assert sum(scored) == total
        assert not session.backlogged
        session.close()

    run(main())


def test_session_counts_flush_dispatches(run):
    """`scoring.dispatches` counts flush-path jit calls (chunks and
    occurrence rounds included) — the megabatch A/B's denominator, so
    the dedicated session must inc the same registry counter the pool
    does (query-path scoring never counts)."""

    async def main():
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=300), tenant_id="t")
        _fill_store(store, sim, 40)
        metrics = MetricsRegistry()
        session = ScoringSession(
            build_model("zscore", window=32), store, metrics,
            ScoringConfig(buckets=(128,), batch_window_ms=0.0))
        session.warmup()
        counter = metrics.counter("scoring.dispatches")
        assert counter.value == 0  # warmup dispatches are not flushes
        batch, _ = sim.tick(t=41 * 60.0)
        session.admit(batch)   # 300 devices > bucket 128 → 3 chunks
        await session.flush()
        assert counter.value == 3
        # megabatch handoff fields default inert on a dedicated session
        assert session.cfg.megabatch_window_ms == 0.0
        assert session.cfg.megabatch_max_tenants == 0
        session.close()

    run(main())


def test_backlog_cap_is_configurable(run):
    """The admission cap is a latency knob (a standing queue of B events
    adds B/rate seconds of tail): default 4 full buckets, overridable
    per tenant via `backlog_cap`."""

    async def main():
        assert ScoringConfig(buckets=(128,)).backlog_events == 512
        assert ScoringConfig(buckets=(128,),
                             backlog_cap=100).backlog_events == 100
        store = TelemetryStore(history=64)
        sim = DeviceSimulator(SimConfig(num_devices=50, seed=1), tenant_id="t")
        _fill_store(store, sim, 40)
        session = ScoringSession(
            build_model("zscore", window=32), store, MetricsRegistry(),
            ScoringConfig(buckets=(128,), backlog_cap=100))
        session.ready = False
        batch, _ = sim.tick(t=40 * 60.0)
        session.admit(batch)  # 50 events < 100
        assert not session.backlogged
        batch, _ = sim.tick(t=41 * 60.0)
        session.admit(batch)  # 100 events >= 100
        assert session.backlogged
        session.close()

    run(main())
