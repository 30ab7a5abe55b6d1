"""Wire data-plane fast-path tests (kernel/wire.py, ISSUE 14).

Three layers under test: streaming poll prefetch (broker-push deliver
frames under a credit window), pipelined micro-batched produce (per-tick
multi-op batch frames with a bounded fire-and-forget window), and the
zero-copy codec path — plus the equivalence re-runs the fast path must
not bend: the fleet kill drill and the straddle exactly-once invariant
from tests/test_fleet.py over a REAL wire broker with prefetch on, and
prefetch-on/off scored-output equivalence over the wire."""

import asyncio

import numpy as np

from sitewhere_tpu.kernel.bus import EventBus
from sitewhere_tpu.kernel.wire import BusServer, RemoteEventBus
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

from tests.test_fleet import DEVICES, _crash, _Meter, fleet
from tests.test_pipeline import wait_until


# ---------------------------------------------------------------------------
# prefetch protocol (no jax, cheap)
# ---------------------------------------------------------------------------


def test_prefetch_streams_without_poll_rpcs(run):
    """With prefetch on, records reach the client through pushed
    deliver frames — the broker sees subscribe/commit/credit ops but
    not one poll RPC per consumer round."""

    async def main():
        bus = EventBus(default_partitions=2)
        server = BusServer(bus)
        polls = 0
        orig = server._op_poll

        async def counting_poll(msg, writer=None):
            nonlocal polls
            polls += 1
            return await orig(msg, writer)

        server.handlers["poll"] = counting_poll
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                prefetch=True, prefetch_credit=32)
        await remote.initialize()
        consumer = remote.subscribe("t", group="g")
        for i in range(20):
            await remote.produce("t", {"i": i}, key=f"k{i % 3}")
        got = []
        while len(got) < 20:
            got += [r.value["i"]
                    for r in await consumer.poll(max_records=8,
                                                 timeout=2.0)]
        assert sorted(got) == list(range(20))
        assert polls == 0, "prefetch mode still issued poll RPCs"
        # long-poll latency: a produce lands in the prefetch buffer
        # without the client asking
        async def later():
            await asyncio.sleep(0.05)
            await remote.produce("t", {"i": 99})

        t = asyncio.get_running_loop().create_task(later())
        t0 = asyncio.get_running_loop().time()
        records = await consumer.poll(max_records=10, timeout=5.0)
        waited = asyncio.get_running_loop().time() - t0
        await t
        assert [r.value["i"] for r in records] == [99]
        assert waited < 1.0
        consumer.close()
        await remote.stop()
        await server.stop()

    run(main())


def test_prefetch_credit_window_bounds_delivery(run):
    """The broker may push at most the granted credit ahead of the
    consumer's drain; draining re-grants and the stream continues."""

    async def main():
        bus = EventBus(default_partitions=1)
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                prefetch=True, prefetch_credit=8)
        await remote.initialize()
        consumer = remote.subscribe("t", group="g")
        # bind the subscription, then flood far past the window
        await consumer.poll(max_records=1, timeout=0.2)
        for i in range(64):
            await remote.produce("t", {"i": i})
        await asyncio.sleep(0.3)
        assert len(consumer._buf) <= 8, (
            f"broker pushed {len(consumer._buf)} records past an "
            f"8-record credit window")
        got = []
        while len(got) < 64:
            batch = await consumer.poll(max_records=16, timeout=2.0)
            assert batch, f"stream stalled at {len(got)}/64"
            got += [r.value["i"] for r in batch]
        assert got == list(range(64))
        consumer.close()
        await remote.stop()
        await server.stop()

    run(main())


def test_prefetch_kill_mid_credit_window_loses_nothing(run):
    """THE kill-drill property at the wire layer: a consumer killed
    (socket dropped, no reconnect, no final commits) with a full credit
    window in flight — some records drained+committed, some drained but
    uncommitted, some still in the prefetch buffer — hands a successor
    exactly every record past the last commit: nothing lost, nothing
    committed-and-replayed."""

    async def main():
        bus = EventBus(default_partitions=1)
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                prefetch=True, prefetch_credit=16)
        await remote.initialize()
        for i in range(50):
            await remote.produce("t", {"i": i})
        consumer = remote.subscribe("t", group="g")
        drained = []
        while len(drained) < 20:
            drained += [r.value["i"] for r in await consumer.poll(
                max_records=min(5, 20 - len(drained)), timeout=2.0)]
        assert drained == list(range(20))
        consumer.commit()  # pins delivered-through: offset 20
        # let the commit batch land, then SIGKILL the client with the
        # credit window mid-flight (buffer holds undrained records)
        await asyncio.sleep(0.2)
        remote._client.kill()
        await asyncio.sleep(0.1)  # broker reaps the dropped peer
        successor_bus = RemoteEventBus("127.0.0.1", server.port,
                                       prefetch=True, prefetch_credit=16)
        await successor_bus.initialize()
        successor = successor_bus.subscribe("t", group="g")
        redelivered = []
        while len(redelivered) < 30:
            batch = await successor.poll(max_records=16, timeout=2.0)
            assert batch, (f"successor stalled at {len(redelivered)}/30: "
                           f"records lost in the killed credit window")
            redelivered += [r.value["i"] for r in batch]
        # exactly the uncommitted suffix, in order: no loss, no replay
        # of the committed prefix
        assert redelivered == list(range(20, 50))
        successor.close()
        await successor_bus.stop()
        await server.stop()

    run(main())


def test_prefetch_revoke_on_rebalance_no_double_delivery(run):
    """A rebalance revokes the credit window: the first member's
    undrained buffer is dropped (those records re-deliver from
    committed offsets) — the group as a whole sees every record, and
    the moved partitions never double-deliver through a stale window."""

    async def main():
        bus = EventBus(default_partitions=4)
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                prefetch=True, prefetch_credit=64)
        await remote.initialize()
        c1 = remote.subscribe("t", group="g")
        await c1.poll(max_records=1, timeout=0.2)  # bind + start push
        for i in range(40):
            await remote.produce("t", {"i": i}, key=f"k{i}")
        # the whole topic fits the credit window: wait until every
        # record sits undrained in c1's buffer
        await wait_until(lambda: len(c1._buf) == 40, timeout=10.0)
        # second member joins: rebalance moves half the partitions.
        # Do NOT drain c1 until its revoke lands — the revoke is what
        # prevents its stale 40-row window from double-delivering
        # beside the post-rebalance re-deliveries.
        c2 = remote.subscribe("t", group="g")
        got1, got2 = [], []
        got2 += [r.value["i"]
                 for r in await c2.poll(max_records=64, timeout=2.0)]
        await wait_until(lambda: len(c1._buf) < 40, timeout=10.0)
        deadline = asyncio.get_event_loop().time() + 10.0
        while (len(got1) + len(got2) < 40
               and asyncio.get_event_loop().time() < deadline):
            got1 += [r.value["i"]
                     for r in await c1.poll(max_records=16, timeout=0.2)]
            got2 += [r.value["i"]
                     for r in await c2.poll(max_records=16, timeout=0.2)]
        # nothing drained before the rebalance and nothing committed →
        # the union must be exactly-once across the member set
        assert sorted(got1 + got2) == list(range(40)), (
            f"double/lost delivery across rebalance: "
            f"{len(got1)}+{len(got2)}")
        c1.close()
        c2.close()
        await remote.stop()
        await server.stop()

    run(main())


def test_prefetch_seek_to_beginning_replays_cleanly(run):
    """A replay consumer (seek-from-beginning, the hermetic-adoption
    path) over prefetch sees the topic exactly once from offset 0 —
    rows pushed before the seek are revoked, not mixed in."""

    async def main():
        bus = EventBus(default_partitions=2)
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                prefetch=True, prefetch_credit=32)
        await remote.initialize()
        for i in range(10):
            await remote.produce("t", {"i": i}, key=f"k{i}")
        # group with committed progress: a fresh member would resume at
        # the committed offsets, a seeking member must NOT
        warm = remote.subscribe("t", group="g")
        got = []
        while len(got) < 10:
            got += [r.value["i"]
                    for r in await warm.poll(max_records=16, timeout=2.0)]
        warm.commit()
        await asyncio.sleep(0.2)
        warm.close()
        await asyncio.sleep(0.1)
        replayer = remote.subscribe("t", group="g")
        replayer.seek_to_beginning()  # before first poll: rides subscribe
        replayed = []
        while len(replayed) < 10:
            batch = await replayer.poll(max_records=16, timeout=2.0)
            assert batch, f"replay stalled at {len(replayed)}/10"
            replayed += [r.value["i"] for r in batch]
        assert sorted(replayed) == list(range(10))
        # and a mid-stream seek replays again without mixing
        replayer.seek_to_beginning()
        again = []
        while len(again) < 10:
            batch = await replayer.poll(max_records=16, timeout=2.0)
            assert batch, f"re-replay stalled at {len(again)}/10"
            again += [r.value["i"] for r in batch]
        assert sorted(again) == list(range(10)), again
        replayer.close()
        await remote.stop()
        await server.stop()

    run(main())


# ---------------------------------------------------------------------------
# pipelined micro-batched produce + the bounded fire-and-forget window
# ---------------------------------------------------------------------------


def test_produce_nowait_coalesces_per_tick(run):
    """N produce_nowait calls in one event-loop tick ride ONE multi-op
    batch frame (no task per op), and every record lands."""

    async def main():
        bus = EventBus(default_partitions=1)
        server = BusServer(bus)
        batches = []
        orig = server._op_batch

        async def counting_batch(msg, writer=None):
            batches.append(len(msg["ops"]))
            return await orig(msg, writer)

        server.handlers["batch"] = counting_batch
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port)
        await remote.initialize()
        for i in range(32):
            remote.produce_nowait("t", {"i": i})
        assert len(remote._client._bg) <= 1, (
            "produce_nowait spawned per-op tasks")
        await wait_until(lambda: bus.end_offsets("t") == [32], timeout=5.0)
        assert max(batches) >= 16, (
            f"ops did not coalesce per tick: batch sizes {batches}")
        assert remote.wire_stats()["frames_coalesced"] >= 16
        await remote.stop()
        await server.stop()

    run(main())


def test_ff_inflight_cap_backpressure_gated_broker(run):
    """SATELLITE regression: against a gated (stalled) broker, the
    fire-and-forget window fills to the cap and `backlogged` turns on —
    no per-op task growth, no unbounded socket writes — and once the
    broker resumes every op lands and the signal clears."""

    async def main():
        bus = EventBus(default_partitions=1)
        server = BusServer(bus)
        gate = asyncio.Event()
        orig = server._op_batch

        async def gated_batch(msg, writer=None):
            await gate.wait()
            return await orig(msg, writer)

        server.handlers["batch"] = gated_batch
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                inflight_cap=16)
        await remote.initialize()
        assert remote.backlogged is False
        for i in range(100):
            remote.produce_nowait("t", {"i": i})
            await asyncio.sleep(0)  # let ticks flush
        await asyncio.sleep(0.1)
        client = remote._client
        assert remote.backlogged is True
        assert client._ff_inflight <= 16, (
            f"{client._ff_inflight} un-acked ops past a 16-op cap")
        # task growth is bounded by the CAP (one ack-handler task per
        # in-flight batch frame), never by the op count — the old
        # task-per-op design would sit at 100 here
        assert len(client._bg) <= 16, (
            f"stalled broker grew {len(client._bg)} background tasks")
        assert client.ff_pending == 100  # nothing dropped
        gate.set()
        await wait_until(lambda: bus.end_offsets("t") == [100],
                         timeout=10.0)
        await wait_until(lambda: not remote.backlogged, timeout=5.0)
        # the signal clears below the cap; the last acks may still be
        # on their way when it does
        await wait_until(lambda: client.ff_pending == 0, timeout=5.0)
        await remote.stop()
        await server.stop()

    run(main())


def test_ff_order_preserved_vs_awaited_frames(run):
    """A fire-and-forget op enqueued BEFORE an awaited produce reaches
    the broker first (the commit-before-release ordering the handoff
    protocol needs), even though the batch frame is assembled at flush
    time."""

    async def main():
        bus = EventBus(default_partitions=1)
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port)
        await remote.initialize()
        remote.produce_nowait("t", {"seq": 0})       # queued this tick
        await remote.produce("t", {"seq": 1})        # same tick, awaited
        await wait_until(lambda: bus.end_offsets("t") == [2], timeout=5.0)
        values = [r.value["seq"] for r in bus.peek("t", limit=-1)]
        assert values == [0, 1], (
            f"awaited frame overtook a queued fire-and-forget op: "
            f"{values}")
        await remote.stop()
        await server.stop()

    run(main())


def test_egress_barrier_surfaces_wire_backpressure():
    """The egress stage folds the wire client's fire-and-forget window
    into its commit-barrier `backlogged` — the consumer-pause signal."""

    class _Bus:
        backlogged = True
        produce_nowait = None

    class _Runtime:
        bus = _Bus()

    from sitewhere_tpu.kernel.egresslane import EgressStage

    stage = object.__new__(EgressStage)
    stage.engine = type("E", (), {"runtime": _Runtime()})()
    stage.submitted = 0
    stage.accounted = 0
    stage.shards = [None]
    assert stage.backlogged is True
    _Bus.backlogged = False
    assert stage.backlogged is False


# ---------------------------------------------------------------------------
# fleet equivalence re-runs over the wire (prefetch on)
# ---------------------------------------------------------------------------


def test_fleet_kill_drill_wire_prefetch_zero_loss(run, tmp_path):
    """tests/test_fleet.py's kill drill over a REAL wire broker with
    prefetch on: the victim dies with a credit window mid-flight
    (socket dropped, no final commits) — reassignment converges and
    every accepted event is scored by somebody (zero loss)."""

    async def main():
        async with fleet(tmp_path, n_workers=2, n_tenants=2,
                         wire=True) as (
                driver, controller, runtimes, workers, cfgs):
            meter = _Meter(driver, cfgs)
            for _ in range(3):
                await meter.submit_round()
            await meter.drain_until_caught_up()

            victim = controller.snapshot()["assignment"]["t0"]
            survivor = next(w for w in workers if w != victim)
            # keep accepting through the crash + reassignment window so
            # the killed credit window has live records in it
            await meter.submit_round()
            await _crash(runtimes, workers, victim)
            for _ in range(4):
                await meter.submit_round()
                await asyncio.sleep(0.05)
            await wait_until(
                lambda: victim not in controller.snapshot()["workers"],
                timeout=30.0)
            await wait_until(
                lambda: controller.snapshot()["converged"], timeout=120.0)
            snap = controller.snapshot()
            assert all(w == survivor for w in snap["assignment"].values())
            for _ in range(2):
                await meter.submit_round()
            await meter.drain_until_caught_up(timeout=120.0)
            # zero lost accepted events (at-least-once: >= is the bound
            # a crash permits; the straddle test pins == for the clean
            # handoff)
            for tid in meter.sent:
                assert meter.scored[tid] >= meter.sent[tid], (
                    tid, meter.sent[tid], meter.scored[tid])
            meter.close()

    run(main())


def test_fleet_straddle_exactly_once_wire_prefetch(run, tmp_path):
    """tests/test_fleet.py's straddle invariant over the wire with
    prefetch on: a clean drain-then-handoff migration under continuous
    flood lands every batch EXACTLY once — the loser's delivered-pin
    commit covers only drained records, its undrained prefetch buffer
    is discarded at close, and the adopter resumes from committed."""

    async def main():
        async with fleet(tmp_path, n_workers=2, n_tenants=2,
                         wire=True) as (
                driver, controller, runtimes, workers, cfgs):
            meter = _Meter(driver, cfgs)
            await meter.submit_round()
            await meter.drain_until_caught_up()

            source = controller.snapshot()["assignment"]["t0"]
            target = next(w for w in workers if w != source)
            controller.migrate("t0", target)
            for _ in range(12):
                await meter.submit_round()
                await asyncio.sleep(0.02)
            await wait_until(
                lambda: controller.snapshot()["owners"].get("t0")
                == target and controller.snapshot()["converged"],
                timeout=60.0)
            for _ in range(2):
                await meter.submit_round()
            await meter.drain_until_caught_up(timeout=120.0)
            # exactly once: scored == sent (< is loss, > is duplicate)
            for tid in meter.sent:
                assert meter.scored[tid] == meter.sent[tid], (
                    tid, meter.sent[tid], meter.scored[tid])
            meter.close()

    run(main())


def test_prefetch_on_off_scored_output_equivalence(run, tmp_path):
    """The fast path must not bend a single score: the same simulator
    traffic through a 1-worker wire fleet produces IDENTICAL scored
    tuples with prefetch/pipelining on and off."""

    async def one_leg(leg_dir, fast):
        outputs = []
        async with fleet(leg_dir, n_workers=1, n_tenants=1,
                         wire=True, wire_prefetch=fast,
                         wire_pipeline=fast) as (
                driver, controller, runtimes, workers, cfgs):
            tid = cfgs[0].tenant_id
            consumer = driver.bus.subscribe(
                driver.naming.tenant_topic(tid, "scored-events"),
                group="equiv-meter")
            receiver = driver.api("event-sources").engine(tid) \
                .receiver("default")
            sim = DeviceSimulator(SimConfig(num_devices=DEVICES, seed=11),
                                  tenant_id=tid)
            sent = 0
            for k in range(6):
                if await receiver.submit(sim.payload(t=3000.0 + k)[0]):
                    sent += DEVICES

            def caught_up():
                for record in consumer.poll_nowait(max_records=256):
                    scored = record.value
                    for i in range(len(scored)):
                        outputs.append((
                            int(scored.device_index[i]),
                            round(float(scored.score[i]), 5),
                            bool(scored.is_anomaly[i])))
                return len(outputs) >= sent

            await wait_until(caught_up, timeout=90.0)
            consumer.close()
        return sorted(outputs)

    async def main():
        on = await one_leg(tmp_path / "on", True)
        off = await one_leg(tmp_path / "off", False)
        assert len(on) == len(off) > 0
        assert on == off, "prefetch changed scored output"

    run(main())


# ---------------------------------------------------------------------------
# zero-copy delivery sanity
# ---------------------------------------------------------------------------


def test_prefetch_delivers_zero_copy_views(run):
    """Delivered batch columns are read-only views over the received
    frame (the zero-copy decode path), and their contents are exact."""
    from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch

    async def main():
        bus = EventBus(default_partitions=1)
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                prefetch=True, prefetch_credit=8)
        await remote.initialize()
        ctx = BatchContext(tenant_id="t", source="s", trace_id=7)
        values = np.linspace(0.0, 1.0, 4096).astype(np.float32)
        batch = MeasurementBatch(
            ctx, np.arange(4096, dtype=np.uint32),
            np.zeros(4096, np.uint16), values,
            np.full(4096, 1700000000.0))
        await remote.produce("t", batch, key="s")
        consumer = remote.subscribe("t", group="g")
        records = []
        while not records:
            records = await consumer.poll(max_records=4, timeout=2.0)
        out = records[0].value
        np.testing.assert_array_equal(out.value, values)
        assert out.ctx.tenant_id == "t" and out.ctx.trace_id == 7
        # the column is a view over the frame, not a copy
        assert out.value.base is not None
        assert not out.value.flags.writeable
        consumer.close()
        await remote.stop()
        await server.stop()

    run(main())


# -- push-loop supervision (swx lint TSK01 regression) -----------------------


def test_push_loop_death_is_supervised(run, caplog):
    """An unexpected escape from a prefetch push loop is logged — the
    pre-fix task died silently, wedging the consumer's credit window
    with no traceback anywhere."""
    import logging

    async def main():
        bus = EventBus(default_partitions=1)
        server = BusServer(bus)

        async def doomed(cid, consumer, writer, st):
            raise RuntimeError("push loop exploded")

        server._push_loop = doomed
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port,
                                prefetch=True, prefetch_credit=8)
        await remote.initialize()
        consumer = remote.subscribe("t", group="g")
        await consumer.poll(max_records=1, timeout=0.3)  # forces subscribe
        await asyncio.sleep(0.05)
        consumer.close()
        await remote.stop()
        await server.stop()

    with caplog.at_level(logging.ERROR, logger="sitewhere_tpu.kernel.wire"):
        run(main())
    assert any("died unexpectedly" in r.getMessage()
               for r in caplog.records)
