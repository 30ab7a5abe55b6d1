"""The device-state merge adapts to its batch (ids ascending on one
channel: no sort, no `ufunc.at`; ids in one run: the tables' own rows)
and leaves the tables bit for bit as a plain per-event loop does; the
state merger spans, times and counts it.
"""

import types

import numpy as np
import pytest

from sitewhere_tpu.config import TenantConfig
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.kernel.tracing import Tracer
from sitewhere_tpu.services.device_state import DeviceStateEngine
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

from tests.test_pipeline import running_pipeline, wait_until

CTX = BatchContext(tenant_id="acme", source="test")


def _engine() -> DeviceStateEngine:
    metrics = MetricsRegistry()
    runtime = types.SimpleNamespace(metrics=metrics,
                                    tracer=Tracer(metrics=metrics))
    return DeviceStateEngine(types.SimpleNamespace(runtime=runtime),
                             TenantConfig(tenant_id="acme"))


def _batch(ids, ts, value=None, mtype=0, dtype=np.uint32):
    ids = np.asarray(ids, dtype)
    n = ids.shape[0]
    rng = np.random.default_rng(n + int(ids.sum() % 9973))
    if value is None:
        value = rng.standard_normal(n)
    return MeasurementBatch(
        CTX, ids, np.broadcast_to(np.asarray(mtype, np.uint16), (n,)).copy(),
        np.asarray(value, np.float32),
        np.broadcast_to(np.asarray(ts, np.float64), (n,)).copy())


def _gaps(n, below, seed=0):
    """n distinct ascending ids under `below`, never one run."""
    ids = np.sort(np.random.default_rng(seed).choice(
        below, size=n, replace=False))
    assert ids[-1] - ids[0] != n - 1
    return ids


def _per_event(batches, rows):
    """The plain reference: one event at a time in arrival order; a
    timestamp not older than what is stored overwrites."""
    last_seen = np.zeros(rows, np.float64)
    channels = {}
    for b in batches:
        for d, m, v, t in zip(b.device_index.tolist(), b.mtype.tolist(),
                              b.value.tolist(), b.ts.tolist()):
            last_seen[d] = max(last_seen[d], t)
            values, tss = channels.setdefault(
                m, (np.zeros(rows, np.float64), np.zeros(rows, np.float64)))
            if t >= tss[d]:
                values[d], tss[d] = v, t
    return last_seen, channels


# name -> (batches, whether each takes neither sort nor ufunc.at)
CASES = {
    "one_run": lambda: (
        [_batch(np.arange(100, 356), 10.0 + k) for k in range(3)],
        [True] * 3),
    "ascending_with_gaps": lambda: (
        [_batch(_gaps(256, 1000, seed=k), 10.0 + k) for k in range(3)],
        [True] * 3),
    "repeated_device": lambda: (
        [_batch(np.arange(64), 10.0),
         _batch([3, 5, 5, 9, 3, 5], [11.0, 13.0, 12.0, 9.0, 11.0, 13.0])],
        [True, False]),
    "two_channels": lambda: (
        [_batch(np.arange(64), 10.0, mtype=np.arange(64) % 2),
         _batch(np.arange(64), 11.0, mtype=1)],
        [False, True]),
    "unsorted_take": lambda: (
        [_batch(np.arange(64)[::-1], np.linspace(10.0, 11.0, 64))],
        [False]),
    "older_than_stored": lambda: (
        [_batch(np.arange(40, 104), 10.0), _batch(np.arange(40, 104), 5.0),
         _batch(_gaps(32, 104, seed=1), 4.0)],
        [True] * 3),
    "equal_timestamps": lambda: (
        [_batch(np.arange(40, 104), 10.0, value=np.full(64, 1.5)),
         _batch(np.arange(40, 104), 10.0, value=np.full(64, 2.5)),
         _batch(_gaps(32, 104, seed=2), 10.0, value=np.full(32, 3.5))],
        [True] * 3),
    "part_older_part_newer_run": lambda: (
        [_batch(np.arange(40, 104), 10.0),
         _batch(np.arange(40, 104), np.where(np.arange(64) % 3, 15.0, 5.0))],
        [True] * 2),
    "part_older_part_newer_gaps": lambda: (
        [_batch(np.arange(0, 200), 10.0),
         _batch(_gaps(64, 200, seed=3),
                np.where(np.arange(64) % 3, 5.0, 15.0))],
        [True] * 2),
    "growth_past_capacity": lambda: (
        [_batch(np.arange(8), 9.0), _batch(np.arange(5000, 5256), 10.0),
         _batch(_gaps(64, 70000, seed=4), 11.0),
         _batch([70001, 70001], [12.0, 12.5])],
        [True, True, True, False]),
    "int64_ids_and_one_event": lambda: (
        [_batch(np.arange(16), 10.0, dtype=np.int64),
         _batch([7], 11.0, dtype=np.int64), _batch([], 12.0)],
        [True] * 3),
}


@pytest.mark.parametrize("columns", ["fresh", "decoded"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_tables_equal_a_per_event_loop(case, columns):
    batches, fast = CASES[case]()
    if columns == "decoded":
        # SWB1's 10-byte header: views at odd offsets of the payload
        batches = [MeasurementBatch.decode(b.encode(), CTX) for b in batches]
        assert not any(b.ts.flags.aligned for b in batches
                       if len(b) % 4 == 0 and len(b))
    engine = _engine()
    assert [engine.merge_measurements(b) for b in batches] == fast
    last_seen, channels = _per_event(batches, engine.capacity)
    assert np.array_equal(engine.last_seen, last_seen)
    assert sorted(engine.last_values) == sorted(channels)
    for mtype, (values, tss) in channels.items():
        assert np.array_equal(engine.last_values[mtype][0], values)
        assert np.array_equal(engine.last_values[mtype][1], tss)
        assert engine.last_values[mtype][0].dtype == np.float64


@pytest.mark.parametrize("columns", ["fresh", "decoded"])
def test_gateway_frames_at_the_top_of_a_fleet_sized_table(columns):
    rows, frame = 524_288, 16_384
    engine = _engine()
    batches = [_batch(np.arange(rows - frame, rows), 10.0),
               _batch(np.arange(rows - 2 * frame, rows - frame), 10.0),
               _batch(np.arange(rows - frame, rows), 11.0)]
    if columns == "decoded":
        batches = [MeasurementBatch.decode(b.encode(), CTX) for b in batches]
    assert all(b.device_index.dtype == np.uint32 for b in batches)
    assert all(engine.merge_measurements(b) for b in batches)
    assert engine.capacity == rows
    last_seen, channels = _per_event(batches, rows)
    assert np.array_equal(engine.last_seen, last_seen)
    assert np.array_equal(engine.last_values[0][0], channels[0][0])
    assert np.array_equal(engine.last_values[0][1], channels[0][1])


def test_the_merger_spans_times_and_counts_each_batch(run):
    async def main():
        async with running_pipeline(num_devices=100) as rt:
            receiver = rt.api("event-sources").engine("acme").receiver(
                "default")
            state = rt.api("device-state").state("acme")
            sim = DeviceSimulator(SimConfig(num_devices=100),
                                  tenant_id="acme")
            # a gateway's frame as it is served: ids ascending, one channel
            await receiver.submit(sim.payload(t=1000.0)[0])
            await wait_until(lambda: state.last_seen[:100].min() == 1000.0)
            merger = state.merger
            assert (merger.merges.value, merger.merges_fast.value) == (1, 1)
            busy = rt.metrics.counter("busy.device-state.merge")
            assert 0 < busy.value == pytest.approx(merger.merge_s.sum)
            assert merger.merge_s.count == 1
            # a frame in which a device reports twice: the general code
            await receiver.submit(
                _batch([4, 9, 9, 12], [1001.0, 1003.0, 1002.0, 1001.0])
                .encode())
            await wait_until(lambda: state.last_seen[9] == 1003.0)
            assert (merger.merges.value, merger.merges_fast.value) == (2, 1)
            assert state.get_state(9)["channels"][0]["ts"] == 1003.0
            assert merger.merge_s.count == 2
            assert busy.value == pytest.approx(merger.merge_s.sum)
            assert merger.merged is rt.metrics.meter(
                "device_state.events_merged")

    run(main())
