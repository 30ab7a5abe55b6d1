"""Sparse anomaly readback (ScoringConfig.readback="anomalies").

Device-side thresholding ships only anomalous (position, score) pairs
host-ward — the TPU-idiomatic answer to the measured D2H readback
ceiling (BASELINE.md). These tests pin: detection parity with full
readback, scratch/bucket-padding masking, duplicate-device rounds,
top-k overflow accounting, and the e2e alert path.
"""

import numpy as np

from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

from tests.test_pipeline import running_pipeline, wait_until
from tests.test_scoring import _fill_store


def _session(store, readback, sparse_k=0, buckets=(256,)):
    s = ScoringSession(
        build_model("lstm-stream", window=64), store, MetricsRegistry(),
        ScoringConfig(buckets=buckets, threshold=4.0, readback=readback,
                      sparse_k=sparse_k, seed=7))
    s.warmup()
    return s


def test_sparse_matches_full_readback(run):
    """Same anomaly set, same scores (fp16 tolerance), per flush —
    including flushes smaller than the bucket (padding masked)."""
    async def main():
        sim = DeviceSimulator(SimConfig(num_devices=200, seed=3),
                              tenant_id="t")
        store_a = TelemetryStore(history=128, initial_devices=200)
        _fill_store(store_a, sim, 70)
        sim2 = DeviceSimulator(SimConfig(num_devices=200, seed=3),
                               tenant_id="t")
        store_b = TelemetryStore(history=128, initial_devices=200)
        _fill_store(store_b, sim2, 70)

        full = _session(store_a, "full")
        sparse = _session(store_b, "anomalies")
        anomaly_cfg = SimConfig(num_devices=200, seed=3,
                                anomaly_rate=0.05, anomaly_magnitude=12.0)
        sim.cfg = anomaly_cfg
        sim2.cfg = anomaly_cfg
        for k in range(5):
            batch, _ = sim.tick(t=(70 + k) * 60.0)
            batch2, _ = sim2.tick(t=(70 + k) * 60.0)
            np.testing.assert_array_equal(batch.value, batch2.value)
            full.admit(batch)
            scored_f = await full.flush()
            sparse.admit(batch2)
            scored_s = await sparse.flush()

            f_anom = {int(d): float(s) for d, s in zip(
                scored_f.device_index[scored_f.is_anomaly],
                scored_f.score[scored_f.is_anomaly])}
            s_anom = {int(d): float(s) for d, s in zip(
                scored_s.device_index, scored_s.score)}
            assert set(s_anom) == set(f_anom)
            for d in f_anom:
                assert abs(s_anom[d] - f_anom[d]) <= \
                    2e-2 * max(1.0, abs(f_anom[d]))
            assert scored_s.is_anomaly.all()
            assert scored_s.total_scored == 200
            assert scored_f.total_scored == -1
        # every event was scored in both modes
        assert full.flights.latency.count == sparse.flights.latency.count == 1000
        full.close()
        sparse.close()

    run(main())


def test_sparse_duplicate_devices_rounds(run):
    """A flush carrying several events for one device scores each
    occurrence (rounds) and reports every anomalous one."""
    async def main():
        store = TelemetryStore(history=128, initial_devices=64)
        sim = DeviceSimulator(SimConfig(num_devices=64, seed=1),
                              tenant_id="t")
        _fill_store(store, sim, 70)
        s = _session(store, "anomalies")
        ctx = BatchContext(tenant_id="t", source="x")
        # device 5 gets two 100-sigma events in ONE flush; device 9 one
        dev = np.array([5, 9, 5], np.uint32)
        vals = np.array([1e4, 1e4, 1e4], np.float32)
        s.admit(MeasurementBatch(ctx, dev, np.zeros(3, np.uint16),
                                 vals, np.full(3, 4300.0)))
        scored = await s.flush()
        assert sorted(scored.device_index.tolist()) == [5, 5, 9]
        assert scored.is_anomaly.all() and (scored.score >= 4.0).all()
        s.close()

    run(main())


def test_sparse_topk_overflow_is_counted(run):
    """More anomalies than k slots: top-k report, overflow counter
    carries the remainder — never a silent truncation."""
    async def main():
        store = TelemetryStore(history=128, initial_devices=200)
        sim = DeviceSimulator(SimConfig(num_devices=200, seed=3),
                              tenant_id="t")
        _fill_store(store, sim, 70)
        s = _session(store, "anomalies", sparse_k=4)
        sim.cfg = SimConfig(num_devices=200, seed=3, anomaly_rate=1.0,
                            anomaly_magnitude=12.0)
        batch, _ = sim.tick(t=70 * 60.0)
        s.admit(batch)
        scored = await s.flush()
        assert len(scored) == 4                      # k slots
        assert s.flights.anomaly_overflow.value > 0
        assert len(scored) + s.flights.anomaly_overflow.value >= 150
        assert scored.total_scored == 200
        s.close()

    run(main())


def test_sparse_multichunk_flush_total_scored(run):
    """A sparse flush larger than the max bucket merges chunks with the
    TRUE scored count (-1 would claim full readback)."""
    async def main():
        store = TelemetryStore(history=128, initial_devices=600)
        sim = DeviceSimulator(SimConfig(num_devices=600, seed=2),
                              tenant_id="t")
        _fill_store(store, sim, 70)
        s = _session(store, "anomalies", buckets=(256,))
        sim.cfg = SimConfig(num_devices=600, seed=2, anomaly_rate=0.02,
                            anomaly_magnitude=12.0)
        batch, truth = sim.tick(t=70 * 60.0)
        s.admit(batch)
        scored = await s.flush()
        assert scored.total_scored == 600          # 3 chunks of ≤256
        assert set(np.nonzero(truth)[0]) <= set(
            scored.device_index.tolist())
        s.close()

    run(main())


def test_sparse_e2e_alert_parity(run):
    """Through the full pipeline, sparse readback emits the same
    model-anomaly alerts the full path does."""
    async def main():
        sections = {
            "event-management": {"history": 128},
            "rule-processing": {"model": "lstm-stream",
                                "model_config": {"window": 32},
                                "threshold": 4.0,
                                "batch_window_ms": 1.0,
                                "buckets": [256], "capacity": 256,
                                "readback": "anomalies"},
        }
        async with running_pipeline(num_devices=100,
                                    sections=sections) as rt:
            em = rt.api("event-management").management("acme")
            eng = rt.api("rule-processing").engine("acme")
            sim = DeviceSimulator(SimConfig(num_devices=100, seed=3),
                                  tenant_id="acme")
            for k in range(36):  # warm history through the store
                batch, _ = sim.tick(t=60.0 * k)
                em.telemetry.append_measurements(batch)
            await wait_until(lambda: eng.session.ready, timeout=60.0)
            eng.session.reload_history()
            sim.cfg = SimConfig(num_devices=100, seed=3,
                                anomaly_rate=0.1, anomaly_magnitude=12.0)
            receiver = rt.api("event-sources").engine("acme") \
                .receiver("default")
            batch, truth = sim.tick(t=60.0 * 40)
            await receiver.submit(batch.encode())
            await wait_until(
                lambda: len([a for a in em.list_alerts()
                             if a.source == "model"]) >= truth.sum(),
                timeout=20.0)
            model_alerts = [a for a in em.list_alerts()
                            if a.source == "model"]
            alert_devs = {em.dm.get_device(a.device_id).index
                          for a in model_alerts if a.device_id}
            assert set(np.nonzero(truth)[0]) <= alert_devs

    run(main())


def test_pool_sparse_matches_pool_full(run):
    """Pooled form (config 4): per-tenant thresholds ride as a device
    vector; sparse pool reports the same anomaly sets the full pool
    does — different alert bars per tenant respected."""
    async def main():
        import jax

        from sitewhere_tpu.scoring.pool import (
            PoolConfig,
            SharedScoringPool,
        )
        from tests.test_streaming import _make_pool_tenant

        model = build_model("lstm-stream", window=64)
        params = {tid: model.init(jax.random.PRNGKey(i + 10))
                  for i, tid in enumerate(("a", "b"))}
        pools = {}
        delivered = {"full": {}, "anomalies": {}}
        stores = {"full": {}, "anomalies": {}}
        sims = {"full": {}, "anomalies": {}}
        for mode in ("full", "anomalies"):
            pool = SharedScoringPool(
                model, MetricsRegistry(),
                PoolConfig(batch_buckets=(64,), batch_window_ms=1.0,
                           readback=mode))
            pools[mode] = pool
            for i, tid in enumerate(("a", "b")):
                # tenant b gets a stricter bar than tenant a
                stores[mode][tid], sims[mode][tid], _ = _make_pool_tenant(
                    pool, tid, 30, i + 20, delivered[mode],
                    params=params[tid],
                    threshold=4.0 if tid == "a" else 6.0)
            await wait_until(lambda p=pool: p.ready, timeout=60.0)

        anomaly = dict(anomaly_rate=0.1, anomaly_magnitude=12.0)
        for k in range(3):
            for mode in ("full", "anomalies"):
                for i, tid in enumerate(("a", "b")):
                    sims[mode][tid].cfg = SimConfig(
                        num_devices=30, seed=i + 20, **anomaly)
                    batch, _ = sims[mode][tid].tick(t=(70 + k) * 60.0)
                    stores[mode][tid].append_measurements(batch)
                    pools[mode].admit(tid, batch)
            await wait_until(
                lambda k=k: all(len(delivered[m][t]) >= k + 1
                                for m in ("full", "anomalies")
                                for t in ("a", "b")), timeout=30.0)
            for tid in ("a", "b"):
                got_f = delivered["full"][tid][k]
                got_s = delivered["anomalies"][tid][k]
                f_anom = {int(d): float(s) for d, s in zip(
                    got_f.device_index[got_f.is_anomaly],
                    got_f.score[got_f.is_anomaly])}
                s_anom = {int(d): float(s) for d, s in zip(
                    got_s.device_index, got_s.score)}
                assert set(s_anom) == set(f_anom), (tid, k)
                for d in f_anom:
                    assert abs(s_anom[d] - f_anom[d]) <= 2e-2 * max(
                        1.0, abs(f_anom[d]))
                assert got_s.total_scored == 30
        for pool in pools.values():
            pool.close()

    run(main())
