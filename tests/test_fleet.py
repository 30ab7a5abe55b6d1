"""Fleet control plane tests (sitewhere_tpu/fleet + parallel/placement).

The ISSUE-10 acceptance surface: deterministic weighted placement, the
drain-then-handoff invariant (old owner's engines stop and commit
BEFORE the new owner starts — never dual-ownership, at-least-once
across the move), automatic reassignment after a worker crash with
zero lost accepted events, the `GET /api/fleet` / `swx fleet status` /
`swx top` surfaces, autoscaler hysteresis/cooldown, and the
fleet.heartbeat / fleet.rebalance chaos sites healing under the
supervisor.

Topology: in-proc — N worker ServiceRuntimes (fleet_managed) share ONE
EventBus with a driver runtime hosting event-sources and the
controller. Same protocol, same records, same consumer groups as the
multi-process deployment (`swx fleet-worker`); only the process
boundary is collapsed. HERMETIC since the fencing PR: tenant registry
state is seeded onto the shared bus (registry-state topic,
services/replication.py) and every worker adopts from bus replay —
each worker's data_dir is worker-LOCAL scratch, never a shared mount.
The fencing tests below pin the epoch-fencing protocol itself
(docs/FLEET.md): stale-epoch writes rejected, zombie owners demoted,
replay-adoption equivalent to snapshot-adoption.
"""

import asyncio
import contextlib

from sitewhere_tpu.cli import render_fleet, render_top
from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.domain.model import DeviceType
from sitewhere_tpu.fleet import AutoscalerPolicy, FleetController, FleetWorker
from sitewhere_tpu.kernel.observe import observe_report
from sitewhere_tpu.kernel.service import ServiceRuntime
from sitewhere_tpu.parallel.placement import (
    compute_placement,
    placement_moves,
    rendezvous_rank,
)
from sitewhere_tpu.services import (
    DeviceManagementService,
    DeviceStateService,
    EventManagementService,
    EventSourcesService,
    InboundProcessingService,
    InstanceManagementService,
    RuleProcessingService,
)
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

from tests.test_pipeline import wait_until

DEVICES = 64

RP_SECTION = {"model": "zscore", "model_config": {"window": 8},
              "threshold": 6.0, "batch_window_ms": 1.0,
              "buckets": [DEVICES], "capacity": DEVICES}


# ---------------------------------------------------------------------------
# placement (pure)
# ---------------------------------------------------------------------------


def test_placement_deterministic_and_stable():
    tenants = {f"t{i}": 1.0 for i in range(40)}
    workers = ["w0", "w1", "w2", "w3"]
    a = compute_placement(tenants, workers)
    b = compute_placement(tenants, list(reversed(workers)))
    assert a == b, "placement must not depend on worker-list order"
    assert set(a) == set(tenants)
    counts = {w: sum(1 for t in a if a[t] == w) for w in workers}
    assert all(c > 0 for c in counts.values()), counts
    # rendezvous stability: removing one worker moves ONLY its tenants
    shrunk = compute_placement(tenants, ["w0", "w1", "w2"])
    moved = placement_moves(a, shrunk)
    assert set(moved) == {t for t, w in a.items() if w == "w3"}, (
        "removing w3 must only move w3's tenants")
    # determinism of the preference order itself
    assert rendezvous_rank("t0", workers) == rendezvous_rank("t0", workers)


def test_placement_respects_weights():
    # one heavy tenant (weight 8) + light ones: the capacity pass must
    # not stack more weight onto the heavy tenant's worker than the
    # headroom cap allows
    tenants = {"heavy": 8.0, **{f"t{i}": 1.0 for i in range(8)}}
    workers = ["w0", "w1"]
    placed = compute_placement(tenants, workers, headroom=1.1)
    load = {w: 0.0 for w in workers}
    for tid, w in placed.items():
        load[w] += tenants[tid]
    cap = 1.1 * sum(tenants.values()) / 2
    assert max(load.values()) <= cap + 8.0  # heavy itself may overshoot
    heavy_worker = placed["heavy"]
    lights_with_heavy = [t for t in placed
                         if placed[t] == heavy_worker and t != "heavy"]
    assert len(lights_with_heavy) <= 2, placed


def test_placement_empty_inputs():
    assert compute_placement({}, ["w0"]) == {}
    assert compute_placement({"t": 1.0}, []) == {}


# ---------------------------------------------------------------------------
# in-proc fleet harness
# ---------------------------------------------------------------------------


def _worker_runtime(bus, wid, data_dir, **overrides):
    rt = ServiceRuntime(InstanceSettings(
        instance_id="fleet-test", fleet_managed=True,
        fleet_heartbeat_s=0.2, observe_interval_ms=50.0,
        # worker-LOCAL scratch (registry WAL + snapshots) — adoption
        # state comes from bus replay, not this directory
        data_dir=str(data_dir / wid), **overrides), bus=bus)
    for cls in (DeviceManagementService, InboundProcessingService,
                EventManagementService, DeviceStateService,
                RuleProcessingService):
        rt.add_service(cls(rt))
    worker = FleetWorker(rt, wid)
    rt.add_child(worker)
    return rt, worker


async def _seed_registries(bus, cfgs, *, instance_id="fleet-test"):
    """Seed each tenant's device registry ONTO THE SHARED BUS
    (replicated tenant state, services/replication.py): the seeding
    runtime's bootstrap registrations land on the per-tenant
    registry-state topic, and whichever worker adopts (initially,
    after a migration, after a crash) rebuilds the same fleet from
    replay — no shared filesystem anywhere (docs/FLEET.md)."""
    seed = ServiceRuntime(InstanceSettings(
        instance_id=instance_id, registry_replication=True), bus=bus)
    seed.add_service(DeviceManagementService(seed))
    await seed.start()
    for cfg in cfgs:
        await seed.add_tenant(cfg)
        dm = seed.api("device-management").management(cfg.tenant_id)
        dm.bootstrap_fleet(DeviceType(token="thermo", name="T"), DEVICES)
    await seed.stop()  # replicator seal: snapshot records on the bus


@contextlib.asynccontextmanager
async def fleet(tmp_path, n_workers=2, n_tenants=2, *, rest=False,
                policy=None, spawner=None, wire=False, wire_prefetch=True,
                wire_pipeline=True, wire_prefetch_credit=64):
    """In-proc fleet harness. With `wire=True` the workers attach to the
    driver's bus over a REAL BusServer socket (RemoteEventBus), so the
    wire data plane — streaming prefetch, pipelined produce, the codec
    — sits under every worker-side record; `wire_prefetch`/
    `wire_pipeline` are the fast-path A/B levers
    (tests/test_wire_prefetch.py re-runs the kill-drill and straddle
    invariants through it)."""
    cfgs = [TenantConfig(tenant_id=f"t{i}",
                         sections={"rule-processing": dict(RP_SECTION)})
            for i in range(n_tenants)]
    driver = ServiceRuntime(InstanceSettings(
        instance_id="fleet-test", fleet_interval_s=0.05,
        fleet_dead_after_s=1.5, rest_port=0))
    driver.add_service(EventSourcesService(driver))
    if rest:
        driver.add_service(InstanceManagementService(driver))
    controller = FleetController(
        driver,
        policy=policy or AutoscalerPolicy(min_workers=n_workers,
                                          max_workers=n_workers),
        spawner=spawner)
    driver.add_child(controller)
    await driver.start()
    broker = None
    if wire:
        from sitewhere_tpu.kernel.wire import BusServer

        broker = BusServer(driver.bus)
        await broker.start()
    await _seed_registries(driver.bus, cfgs)
    workers = {}
    runtimes = {}
    for i in range(n_workers):
        wid = f"w{i}"
        bus = driver.bus
        if wire:
            from sitewhere_tpu.kernel.wire import RemoteEventBus

            bus = RemoteEventBus("127.0.0.1", broker.port,
                                 prefetch=wire_prefetch,
                                 pipeline=wire_pipeline,
                                 prefetch_credit=wire_prefetch_credit)
            bus.owner = wid
        rt, worker = _worker_runtime(bus, wid, tmp_path)
        await rt.start()
        runtimes[wid] = rt
        workers[wid] = worker
    for cfg in cfgs:
        # local event-sources engines + (the driver hosts the
        # controller) fleet placement registration, one call
        await driver.add_tenant(cfg)
    await wait_until(lambda: controller.snapshot()["converged"],
                     timeout=120.0)
    try:
        yield driver, controller, runtimes, workers, cfgs
    finally:
        for rt in runtimes.values():
            if rt.status.value != "stopped":
                await rt.stop()
        if broker is not None:
            await broker.stop()
        await driver.stop()


class _Meter:
    """Scored-events counters per tenant off the shared bus."""

    def __init__(self, driver, cfgs):
        self.consumers = {c.tenant_id: driver.bus.subscribe(
            driver.naming.tenant_topic(c.tenant_id, "scored-events"),
            group="fleet-test-meter") for c in cfgs}
        self.scored = {c.tenant_id: 0 for c in cfgs}
        self.sent = {c.tenant_id: 0 for c in cfgs}
        self.sims = {c.tenant_id: DeviceSimulator(
            SimConfig(num_devices=DEVICES), tenant_id=c.tenant_id)
            for c in cfgs}
        self.driver = driver
        self._k = 0

    async def submit_round(self):
        for tid, sim in self.sims.items():
            receiver = self.driver.api("event-sources") \
                .engine(tid).receiver("default")
            if await receiver.submit(sim.payload(t=1000.0 + self._k)[0]):
                self.sent[tid] += DEVICES
        self._k += 1

    def drain(self):
        for tid, consumer in self.consumers.items():
            for record in consumer.poll_nowait(max_records=256):
                self.scored[tid] += len(record.value)

    async def drain_until_caught_up(self, timeout=90.0):
        def caught_up():
            self.drain()
            return all(self.scored[t] >= self.sent[t] for t in self.sent)

        await wait_until(caught_up, timeout=timeout)

    def close(self):
        for consumer in self.consumers.values():
            consumer.close()


async def _crash(runtimes, workers, wid):
    """Kill a worker with crash fidelity: no leave, no releases — its
    loops just stop and its engines vanish (in-proc stand-in for
    SIGKILL; the consumers leave their groups exactly as the broker's
    on_disconnect reaps a dead wire peer's). On a wire-attached worker
    the client is KILLED first (socket drops, no reconnect, no final
    commits), so the broker sees exactly what a SIGKILLed process
    leaves behind — including a prefetch credit window mid-flight."""
    worker = workers.pop(wid)
    rt = runtimes.pop(wid)
    client = getattr(rt.bus, "_client", None)
    if client is not None:
        client.kill()
    for loop in (worker._control, worker._apply):
        if loop._task is not None:
            loop._task.cancel()
    worker.owned.clear()          # _do_stop must not release/announce
    rt.remove_child(worker)
    try:
        await rt.stop()
    except Exception:  # noqa: BLE001 - crash fidelity: a SIGKILLed
        # process runs no stop path at all; with the wire client killed,
        # stop-path produces (replicator seal, final commits) fail — the
        # partial teardown IS the crash being simulated
        if client is None:
            raise


# ---------------------------------------------------------------------------
# handoff invariant: migration
# ---------------------------------------------------------------------------


def test_fleet_migration_drain_then_handoff(run, tmp_path):
    async def main():
        async with fleet(tmp_path, n_workers=2, n_tenants=2) as (
                driver, controller, runtimes, workers, cfgs):
            meter = _Meter(driver, cfgs)
            for _ in range(4):
                await meter.submit_round()
            await meter.drain_until_caught_up()
            before = dict(meter.scored)
            assert all(v > 0 for v in before.values())

            # migrate t0 to the worker that does NOT own it
            source = controller.snapshot()["assignment"]["t0"]
            target = next(w for w in workers if w != source)
            controller.migrate("t0", target)
            await wait_until(
                lambda: controller.snapshot()["assignment"].get("t0")
                == target and controller.snapshot()["converged"],
                timeout=60.0)

            # THE invariant: the old owner released (engines stopped,
            # release published) strictly before the new owner adopted
            assert workers[source].released_at["t0"] \
                <= workers[target].adopted_at["t0"]
            assert "t0" not in runtimes[source].tenants
            assert "t0" in runtimes[target].tenants

            # committed-offset resume: post-migration traffic scores
            # (and nothing accepted before the move was lost)
            for _ in range(3):
                await meter.submit_round()
            await meter.drain_until_caught_up()
            assert meter.scored["t0"] >= meter.sent["t0"]

            # handoff accounting
            snap = driver.metrics.snapshot()
            assert snap.get("fleet.rebalances", 0) >= 2
            assert runtimes[target].metrics.counter(
                "fleet.handoffs").value >= 1
            meter.close()

    run(main())


# ---------------------------------------------------------------------------
# worker death: reassignment, zero loss, operator surfaces
# ---------------------------------------------------------------------------


def test_worker_crash_reassigns_with_zero_loss(run, tmp_path):
    async def main():
        async with fleet(tmp_path, n_workers=2, n_tenants=2,
                         rest=True) as (
                driver, controller, runtimes, workers, cfgs):
            meter = _Meter(driver, cfgs)
            for _ in range(3):
                await meter.submit_round()
            await meter.drain_until_caught_up()

            # kill the worker owning t0 MID-FLOOD: keep accepting events
            # through the crash and the reassignment window
            victim = controller.snapshot()["assignment"]["t0"]
            survivor = next(w for w in workers if w != victim)
            await meter.submit_round()
            await _crash(runtimes, workers, victim)
            for _ in range(4):
                await meter.submit_round()
                await asyncio.sleep(0.05)

            # the controller declares the victim dead and reassigns;
            # the survivor adopts WITHOUT waiting on a release (the
            # dead cannot ack) and resumes from committed offsets
            await wait_until(
                lambda: victim not in controller.snapshot()["workers"],
                timeout=30.0)
            await wait_until(
                lambda: controller.snapshot()["converged"], timeout=120.0)
            snap = controller.snapshot()
            assert all(w == survivor for w in snap["assignment"].values())
            assert driver.metrics.counter("fleet.worker_deaths").value >= 1

            # zero lost accepted events: everything the ingress accepted
            # is scored (exactly-once-or-replayed — scored >= accepted)
            for _ in range(2):
                await meter.submit_round()
            await meter.drain_until_caught_up(timeout=120.0)
            for tid in meter.sent:
                assert meter.scored[tid] >= meter.sent[tid], (
                    tid, meter.sent[tid], meter.scored[tid])

            # operator surfaces reflect the new placement:
            # GET /api/fleet over real HTTP...
            from tests.test_fleet import _http_get_fleet

            report = await _http_get_fleet(driver)
            assert set(report["workers"]) == {survivor}
            assert all(w == survivor
                       for w in report["assignment"].values())
            # ...and the swx top / swx fleet renderings
            text = render_fleet(report)
            assert survivor in text and "fleet epoch" in text
            top = render_top(observe_report(driver))
            assert "fleet epoch" in top and survivor in top
            meter.close()

    run(main())


async def _http_get_fleet(driver) -> dict:
    """JWT dance + GET /api/fleet against the driver's live REST port."""
    import base64
    import json as _json

    from sitewhere_tpu.cli import _http_json

    port = driver.services["instance-management"].rest.port
    basic = base64.b64encode(b"admin:password").decode()
    status, out = await _http_json(
        "POST", "127.0.0.1", port, "/api/jwt",
        headers={"Authorization": f"Basic {basic}"})
    assert status == 200, (status, out)
    status, report = await _http_json(
        "GET", "127.0.0.1", port, "/api/fleet",
        headers={"Authorization": f"Bearer {out['token']}"})
    assert status == 200, (status, report)
    return _json.loads(_json.dumps(report))


# ---------------------------------------------------------------------------
# autoscaler decisions (hysteresis + cooldown)
# ---------------------------------------------------------------------------


def test_autoscaler_decisions_hysteresis_and_cooldown():
    rt = ServiceRuntime(InstanceSettings(instance_id="fleet-unit"))
    controller = FleetController(rt, policy=AutoscalerPolicy(
        min_workers=1, max_workers=4, scale_up_lag=1000.0,
        scale_down_lag=100.0, hysteresis=0.8, cooldown_s=10.0,
        imbalance_ratio=3.0))
    controller._last_scale_t = -1e9

    # scale up: mean load per worker above the up threshold
    decision = controller.decide({"w0": 3000.0, "w1": 100.0}, {})
    assert decision and decision["action"] == "add_replica"

    # cooldown: an immediately-following decision is suppressed
    import time

    controller._last_scale_t = time.monotonic()
    assert controller.decide({"w0": 9000.0, "w1": 9000.0}, {}) is None
    controller._last_scale_t = -1e9

    # hysteresis band: below up, above down×hysteresis → hold
    assert controller.decide({"w0": 150.0, "w1": 150.0}, {}) is None

    # scale down: quiet fleet sheds its coolest worker
    decision = controller.decide({"w0": 10.0, "w1": 50.0}, {})
    assert decision and decision["action"] == "remove_replica"
    assert decision["worker"] == "w0"

    # replace-below-floor ignores cooldown (a dead worker must be
    # replaced promptly)
    controller._last_scale_t = time.monotonic()
    decision = controller.decide({}, {})
    assert decision and decision["action"] == "add_replica"

    # migration: one hot worker owning several tenants, fleet balanced
    # enough that a move beats a new replica
    from sitewhere_tpu.fleet.controller import _WorkerState

    controller._last_scale_t = -1e9
    controller.tenants = {"a": None, "b": None, "c": None}
    controller.workers = {
        "w0": _WorkerState(last_seen=time.monotonic(),
                           owned=("a", "b"), signals={}),
        "w1": _WorkerState(last_seen=time.monotonic(),
                           owned=("c",), signals={}),
    }
    decision = controller.decide({"w0": 700.0, "w1": 10.0},
                                 {"a": 650.0, "b": 50.0, "c": 10.0})
    assert decision and decision["action"] == "migrate_tenant", decision
    assert decision["tenant"] == "a" and decision["worker"] == "w1"


def test_worker_retirement_drains_and_exits(run, tmp_path):
    """Scale-down end to end: a retired worker keeps heartbeating (so
    peers can still wait on its releases), hands every tenant to the
    survivors, and flags itself retired — the process entry exits on
    that flag."""

    async def main():
        async with fleet(tmp_path, n_workers=2, n_tenants=2) as (
                driver, controller, runtimes, workers, cfgs):
            meter = _Meter(driver, cfgs)
            await meter.submit_round()
            await meter.drain_until_caught_up()

            victim = controller.snapshot()["assignment"]["t0"]
            survivor = next(w for w in workers if w != victim)
            controller.retire_worker(victim)
            await wait_until(lambda: workers[victim].retired,
                             timeout=60.0)
            snap = controller.snapshot()
            assert all(w == survivor for w in snap["assignment"].values())
            assert not runtimes[victim].tenants
            # drain-then-handoff held through the retirement
            for tid in snap["assignment"]:
                if tid in workers[victim].released_at \
                        and tid in workers[survivor].adopted_at:
                    assert workers[victim].released_at[tid] \
                        <= workers[survivor].adopted_at[tid]
            # traffic still scores on the survivor
            await meter.submit_round()
            await meter.drain_until_caught_up()
            meter.close()

    run(main())


# ---------------------------------------------------------------------------
# chaos: the fleet's own fault sites heal under the supervisor
# ---------------------------------------------------------------------------


def test_fleet_chaos_sites_heal(run, tmp_path):
    from sitewhere_tpu.kernel.faults import FaultInjector

    async def main():
        async with fleet(tmp_path, n_workers=1, n_tenants=1) as (
                driver, controller, runtimes, workers, cfgs):
            wid, rt = next(iter(runtimes.items()))

            # fleet.heartbeat: the worker's control loop crashes once,
            # restarts under the supervisor, and heartbeats resume —
            # the worker is never declared dead
            rt.install_faults(FaultInjector(seed=3).arm(
                "fleet.heartbeat", rate=1.0, max_faults=1))
            seq_before = controller.workers[wid].seq
            await wait_until(
                lambda: workers[wid]._control.restart_count >= 1,
                timeout=30.0)
            await wait_until(
                lambda: controller.workers.get(wid) is not None
                and controller.workers[wid].seq > seq_before + 1,
                timeout=30.0)
            assert wid in controller.snapshot()["workers"]

            # fleet.rebalance: the controller loop crashes mid-publish,
            # restarts, recovers its epoch off the control topic, and
            # the pending rebalance still lands
            driver.install_faults(FaultInjector(seed=4).arm(
                "fleet.rebalance", rate=1.0, max_faults=1))
            epoch_before = controller.epoch
            extra = TenantConfig(tenant_id="late",
                                 sections={"rule-processing":
                                           dict(RP_SECTION)})
            await driver.add_tenant(extra)  # CRUD feeds placement
            await wait_until(
                lambda: controller._loop.restart_count >= 1, timeout=30.0)
            await wait_until(
                lambda: controller.snapshot()["assignment"].get("late")
                == wid, timeout=60.0)
            assert controller.epoch > epoch_before
            # the injected crashes were quarantine-free (no poison
            # record involved) and bounded — the fleet is converged
            await wait_until(
                lambda: controller.snapshot()["converged"], timeout=60.0)

    run(main())


# ---------------------------------------------------------------------------
# epoch fencing (docs/FLEET.md fencing protocol)
# ---------------------------------------------------------------------------


def test_fence_authority_rules(run):
    """The broker-side ownership table mirrors drain-then-handoff:
    old owner fenced-in until its release while live, fenced OUT
    immediately when the placement says it is dead — and a stale-epoch
    produce/commit raises the DISTINCT FencedError, never a generic
    failure."""
    from sitewhere_tpu.kernel.bus import EventBus, FencedError

    async def main():
        bus = EventBus()
        ctl = "fx.instance.fleet-control"
        topic = "fx.tenant.t0.inbound-events"
        await bus.produce(ctl, {"kind": "placement", "epoch": 1,
                                "assignment": {"t0": "w0"},
                                "workers": ["w0", "w1"]})
        # the owner writes
        await bus.produce(topic, {"n": 1}, fence=["t0", 1, "w0"])
        # unfenced writes (ingress, control plane) always pass
        await bus.produce(topic, {"n": 2})
        # move t0 to w1 with w0 LIVE and actually owning (prev map —
        # the controller's actual-owner view): w0 keeps writing through
        # its drain; w1 must NOT write before the release
        await bus.produce(ctl, {"kind": "placement", "epoch": 2,
                                "assignment": {"t0": "w1"},
                                "prev": {"t0": "w0"},
                                "workers": ["w0", "w1"]})
        await bus.produce(topic, {"n": 3}, fence=["t0", 1, "w0"])
        import pytest

        with pytest.raises(FencedError):
            await bus.produce(topic, {"n": 4}, fence=["t0", 2, "w1"])
        # release transfers ownership; the zombie's next write rejects
        await bus.produce(ctl, {"kind": "release", "tenant": "t0",
                                "worker": "w0", "epoch": 2})
        await bus.produce(topic, {"n": 5}, fence=["t0", 2, "w1"])
        with pytest.raises(FencedError) as exc_info:
            await bus.produce(topic, {"n": 6}, fence=["t0", 1, "w0"])
        assert exc_info.value.tenant == "t0"
        # dead old owner: the transfer is IMMEDIATE (the zombie window
        # closed by construction, no release needed from a corpse)
        await bus.produce(ctl, {"kind": "placement", "epoch": 3,
                                "assignment": {"t0": "w0"},
                                "prev": {"t0": "w1"},
                                "workers": ["w0"]})  # w1 dead
        with pytest.raises(FencedError):
            await bus.produce(topic, {"n": 7}, fence=["t0", 2, "w1"])
        await bus.produce(topic, {"n": 8}, fence=["t0", 3, "w0"])
        # stale-epoch COMMIT rejected too — a zombie can never move a
        # tenant group's offsets (the loss direction of dual ownership)
        consumer = bus.subscribe(topic, group="t0.inbound-processing")
        consumer.poll_nowait()
        before = dict(bus._groups["t0.inbound-processing"].committed)
        with pytest.raises(FencedError):
            consumer.commit(fence=["t0", 2, "w1"])
        assert bus._groups["t0.inbound-processing"].committed == before
        consumer.commit(fence=["t0", 3, "w0"])
        assert bus._groups["t0.inbound-processing"].committed != before
        assert bus.fences.rejections >= 4
        # assignment churn before the first assignee ever adopted: the
        # authority must key off the ACTUAL owner (`prev`), not the
        # assignment — or the rightful adopter waits on a release from
        # a worker that never owned the tenant (the measured wedge:
        # adopt → fence → release loop on a replacement worker)
        await bus.produce(ctl, {"kind": "placement", "epoch": 4,
                                "assignment": {"t0": "w1"},
                                "prev": {"t0": "w0"},
                                "workers": ["w0", "w1"]})
        await bus.produce(ctl, {"kind": "placement", "epoch": 5,
                                "assignment": {"t0": "w2"},
                                "prev": {},  # w0 released; nobody owns
                                "workers": ["w0", "w1", "w2"]})
        # w2 never waits on w1 (which never owned t0): write accepted
        await bus.produce(topic, {"n": 9}, fence=["t0", 5, "w2"])
        consumer.close()

    run(main())


def test_zombie_owner_fenced_and_demoted(run, tmp_path):
    """THE dual-ownership window, closed: a worker that goes deaf+mute
    (SIGSTOP analog — heartbeats stop, placements unseen) past
    dead_after is declared dead and its tenants reassign; when its
    engines keep consuming on stale state, the broker REJECTS their
    writes (fenced), the worker self-demotes (stops engines, publishes
    no release), and nothing accepted is lost."""

    async def main():
        async with fleet(tmp_path, n_workers=2, n_tenants=2) as (
                driver, controller, runtimes, workers, cfgs):
            meter = _Meter(driver, cfgs)
            for _ in range(3):
                await meter.submit_round()
            await meter.drain_until_caught_up()

            victim = controller.snapshot()["assignment"]["t0"]
            survivor = next(w for w in workers if w != victim)
            zombie = workers[victim]
            zombie_rt = runtimes[victim]

            # zombify: heartbeats stop, control records unseen — but the
            # engines (consumer loops, scoring, egress) keep running on
            # the stale placement view. This is SIGSTOP-then-SIGCONT
            # fidelity without the process boundary.
            async def _mute():
                return None

            zombie.heartbeat = _mute
            zombie.handle_control = lambda value: None

            # keep traffic flowing through the death + reassignment
            # window so the zombie has live records to (try to) write
            rejections0 = (driver.bus.fences.rejections
                           if driver.bus.fences is not None else 0)
            for _ in range(40):
                await meter.submit_round()
                await asyncio.sleep(0.05)
                if victim not in controller.snapshot()["workers"]:
                    break
            assert victim not in controller.snapshot()["workers"], \
                "controller never declared the mute worker dead"

            # the survivor adopts (dead owners can't ack) and the
            # zombie's fenced engines are stopped by its own apply loop
            await wait_until(
                lambda: "t0" not in zombie_rt.tenants
                and zombie_rt.fence.token("t0") is None, timeout=60.0)
            await wait_until(
                lambda: controller.snapshot()["owners"].get("t0")
                == survivor, timeout=60.0)
            # the zombie TRIED to write and was refused — the window is
            # closed by rejection, not by a grace timer
            assert driver.bus.fences is not None
            assert driver.bus.fences.rejections > rejections0
            assert driver.metrics.counter("fence.rejections").value > 0
            # the fenced demotion published NO release record under the
            # stale epoch — ownership moved via the fence authority
            fencing = controller.snapshot()["fencing"]
            assert fencing["owners"]["t0"]["worker"] == survivor

            # zero lost accepted events: everything accepted through
            # the false-positive death is scored by somebody
            for _ in range(2):
                await meter.submit_round()
            await meter.drain_until_caught_up(timeout=120.0)
            for tid in meter.sent:
                assert meter.scored[tid] >= meter.sent[tid], (
                    tid, meter.sent[tid], meter.scored[tid])
            meter.close()

    run(main())


def test_inflight_straddle_lands_exactly_once(run, tmp_path):
    """A drain-then-handoff migration under continuous flood: batches
    in flight when the epoch bumps land EXACTLY once — the loser's
    release commits through its settle barrier before the adopter
    resumes from committed offsets, so a clean handoff produces zero
    replays and zero losses (the at-least-once bound tightens to
    exactly-once when nobody crashes)."""

    async def main():
        async with fleet(tmp_path, n_workers=2, n_tenants=2) as (
                driver, controller, runtimes, workers, cfgs):
            meter = _Meter(driver, cfgs)
            await meter.submit_round()
            await meter.drain_until_caught_up()

            source = controller.snapshot()["assignment"]["t0"]
            target = next(w for w in workers if w != source)
            controller.migrate("t0", target)
            # flood WHILE the handoff runs: some batches straddle the
            # epoch bump (admitted by the loser, scored by either side)
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
            # exactly once: scored == sent (>= is loss, > is duplicate)
            for tid in meter.sent:
                assert meter.scored[tid] == meter.sent[tid], (
                    tid, meter.sent[tid], meter.scored[tid])
            meter.close()

    run(main())


# ---------------------------------------------------------------------------
# replicated tenant state: hermetic adoption + the WAL crash bound
# ---------------------------------------------------------------------------


def test_adoption_by_replay_equals_adoption_by_snapshot(run, tmp_path):
    """The state-equivalence pin: a worker with an EMPTY local data_dir
    adopting from bus replay ends with the same registry — and scores
    the same events identically — as one restoring the legacy shared
    registry.snap."""
    import numpy as np

    from sitewhere_tpu.kernel.bus import EventBus

    def _norm(snap):
        return {name: sorted((e.id, getattr(e, "token", ""),
                              getattr(e, "index", -1),
                              getattr(e, "status", ""))
                             for e in snap["tables"][name])
                for name in snap["tables"]}

    async def _build(instance_id, bus, settings_kw, cfg):
        rt = ServiceRuntime(InstanceSettings(
            instance_id=instance_id, **settings_kw), bus=bus)
        for cls in (DeviceManagementService, EventSourcesService,
                    InboundProcessingService, EventManagementService,
                    DeviceStateService, RuleProcessingService):
            rt.add_service(cls(rt))
        await rt.start()
        await rt.add_tenant(cfg)
        return rt

    async def _score_round(rt, tid, sim):
        consumer = rt.bus.subscribe(
            rt.naming.tenant_topic(tid, "scored-events"),
            group="equiv-meter")
        receiver = rt.api("event-sources").engine(tid).receiver("default")
        sent = 0
        # one device is deactivated below: each submit scores
        # DEVICES - 1 events (the unregistered split drops the rest)
        for k in range(3):
            if await receiver.submit(sim.payload(t=2000.0 + k)[0]):
                sent += DEVICES - 1
        out = []

        def caught_up():
            for record in consumer.poll_nowait(max_records=256):
                scored = record.value
                for i in range(len(scored)):
                    out.append((int(scored.device_index[i]),
                                round(float(scored.score[i]), 5),
                                bool(scored.is_anomaly[i])))
            return len(out) >= sent

        await wait_until(caught_up, timeout=60.0)
        consumer.close()
        return sorted(out)

    async def main():
        shared = tmp_path / "shared"
        cfg = TenantConfig(tenant_id="eq",
                           sections={"rule-processing": dict(RP_SECTION)})
        # seed: replication on AND a disk snapshot — the same history
        # feeds both adoption paths
        seed_bus = EventBus()
        seed = ServiceRuntime(InstanceSettings(
            instance_id="equiv", data_dir=str(shared),
            registry_replication=True), bus=seed_bus)
        seed.add_service(DeviceManagementService(seed))
        await seed.start()
        await seed.add_tenant(cfg)
        dm = seed.api("device-management").management("eq")
        dm.bootstrap_fleet(DeviceType(token="thermo", name="T"), DEVICES)
        # a post-bootstrap mutation both paths must carry (status
        # matters: the registered mask gates scoring)
        dm.set_device_status(dm.get_device_by_token("dev-1").id,
                             "inactive")
        expected = _norm(dm.spi.to_snapshot())
        await seed.stop()

        # path A — bus replay: EMPTY local data_dir, same bus
        rt_a = await _build(
            "equiv", seed_bus,
            {"registry_replication": True}, cfg)
        dm_a = rt_a.api("device-management").management("eq")
        assert dm_a.restored_from == "bus-replay"
        # path B — legacy shared snapshot: fresh bus, shared data_dir
        rt_b = await _build(
            "equiv", EventBus(),
            {"registry_replication": False, "data_dir": str(shared)},
            cfg)
        dm_b = rt_b.api("device-management").management("eq")
        assert dm_b.restored_from == "snapshot+wal"

        assert _norm(dm_a.spi.to_snapshot()) == expected
        assert _norm(dm_b.spi.to_snapshot()) == expected
        idx = np.arange(DEVICES)
        assert (dm_a.registered_mask(idx) == dm_b.registered_mask(idx)).all()
        assert not dm_a.registered_mask(np.asarray([1]))[0]

        sim = DeviceSimulator(SimConfig(num_devices=DEVICES),
                              tenant_id="eq")
        scored_a = await _score_round(rt_a, "eq", sim)
        sim_b = DeviceSimulator(SimConfig(num_devices=DEVICES),
                                tenant_id="eq")
        scored_b = await _score_round(rt_b, "eq", sim_b)
        assert scored_a == scored_b and scored_a, (
            len(scored_a), len(scored_b))
        await rt_a.stop()
        await rt_b.stop()

    run(main())


def test_registry_wal_tightens_crash_bound(run, tmp_path):
    """Registrations after the last snapshot survive a hard crash via
    the WAL: the crash bound is the last APPENDED record, not the
    snapshot interval."""

    async def main():
        data = tmp_path / "node"
        rt = ServiceRuntime(InstanceSettings(
            instance_id="walcrash", data_dir=str(data)))
        rt.add_service(DeviceManagementService(rt))
        await rt.start()
        # huge snapshot interval: the debounced snapshotter can never
        # run before the "crash" below
        await rt.add_tenant(TenantConfig(
            tenant_id="t0",
            sections={"device-management":
                      {"snapshot_interval_s": 3600.0}}))
        dm = rt.api("device-management").management("t0")
        dm.bootstrap_fleet(DeviceType(token="thermo", name="T"), 8)
        assert rt.metrics.counter("fence.wal_appends").value > 0
        # HARD CRASH: no engine stop, no save_now — abandon the runtime
        # (the WAL fsynced every mutation as it happened)
        wal_path = data / "tenants" / "t0" / "registry.wal"
        assert wal_path.exists() and wal_path.stat().st_size > 0
        snap_path = data / "tenants" / "t0" / "registry.snap"
        assert not snap_path.exists()

        rt2 = ServiceRuntime(InstanceSettings(
            instance_id="walcrash2", data_dir=str(data)))
        rt2.add_service(DeviceManagementService(rt2))
        await rt2.start()
        await rt2.add_tenant(TenantConfig(tenant_id="t0"))
        dm2 = rt2.api("device-management").management("t0")
        assert dm2.restored_from == "snapshot+wal"
        assert dm2.spi.device_count() == 8
        assert dm2.spi.get_device_by_token("dev-3") is not None
        import numpy as np

        assert dm2.registered_mask(np.arange(8)).all()
        await rt2.stop()
        # engines from the abandoned runtime hold the old WAL file open;
        # that is fine — replay reads by path
        for svc in rt.services.values():
            svc.engines.clear()

    run(main())


# ---------------------------------------------------------------------------
# wire surface: the broker serves group lags to remote peers
# ---------------------------------------------------------------------------


def test_wire_group_lags_op(run):
    from sitewhere_tpu.kernel.bus import EventBus
    from sitewhere_tpu.kernel.wire import BusServer, RemoteEventBus

    async def main():
        bus = EventBus()
        await bus.produce("fleet-test.tenant.acme.inbound-events", {"n": 1},
                          key="d1")
        consumer = bus.subscribe("fleet-test.tenant.acme.inbound-events",
                                 group="acme.inbound-processing")
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port)
        await remote.initialize()
        import inspect

        lags = remote.group_lags()
        assert inspect.isawaitable(lags)
        lag_map = await lags
        assert lag_map["acme.inbound-processing"][
            "fleet-test.tenant.acme.inbound-events"] == 1
        consumer.close()
        await remote.stop()
        await server.stop()

    run(main())


# ---------------------------------------------------------------------------
# broker-side member eviction on death declarations (kernel/bus.py)


def test_broker_evicts_dead_workers_members(run):
    """ROADMAP item 4's remaining thread, closed: a placement record
    that DROPS a worker from the live list (the controller's death
    declaration) evicts that worker's owner-tagged consumer-group
    members broker-side — the zombie's partitions reassign to surviving
    members NOW instead of stalling until SIGCONT, its late commits are
    refused, and its polls read nothing through the stale assignment."""
    import pytest

    from sitewhere_tpu.kernel.bus import EventBus
    from sitewhere_tpu.kernel.metrics import MetricsRegistry

    async def main():
        bus = EventBus(default_partitions=4)
        bus.metrics = MetricsRegistry()
        topic = "swx1.tenant.t0.outbound-enriched-events"
        control = "swx1.instance.fleet-control"
        zombie = bus.subscribe(topic, group="t0.rule-processing",
                               owner="w0")
        await bus.produce(control, {
            "kind": "placement", "epoch": 1,
            "assignment": {"t0": "w0"}, "prev": {},
            "workers": ["w0", "w1"]}, key="placement")
        for i in range(8):
            await bus.produce(topic, {"n": i}, key=f"d{i}")
        # the successor joins the SAME group: without eviction the
        # rebalance splits partitions 2/2 with a member that can never
        # poll again — half the topic stalls
        successor = bus.subscribe(topic, group="t0.rule-processing",
                                  owner="w1")
        assert len(zombie.assignment) == 2
        assert len(successor.assignment) == 2
        # w0's own fleet-control subscription (broadcast group, no
        # partition contention) must SURVIVE its eviction: a falsely
        # declared worker that resumes still needs to see placements
        control_sub = bus.subscribe(control, group="fleet.worker.w0",
                                    owner="w0")
        # the death declaration: w0 absent from the live-worker list
        await bus.produce(control, {
            "kind": "placement", "epoch": 2,
            "assignment": {"t0": "w1"}, "prev": {"t0": "w0"},
            "workers": ["w1"]}, key="placement")
        assert zombie.evicted and zombie._closed
        assert len(successor.assignment) == 4  # all partitions, now
        assert bus.metrics.counter("fleet.members_evicted").value == 1
        # the control subscription rode through: not evicted, still
        # assigned, still reading (resumed workers stay reachable)
        assert not control_sub.evicted and not control_sub._closed
        assert control_sub.poll_nowait(max_records=8)
        # the zombie's stale assignment reads nothing...
        assert zombie.poll_nowait(max_records=64) == []
        # ...and its late commit is refused (the unfenced-group analog
        # of the data-path FencedError)
        with pytest.raises(RuntimeError, match="evicted"):
            zombie.commit({(topic, 0): 5})
        # a FENCED commit still raises the TYPED error (fence checked
        # BEFORE the eviction refusal): the wire client's on_fenced
        # signal path — the worker's "you lost ownership" — survives
        # eviction
        from sitewhere_tpu.kernel.bus import FencedError

        with pytest.raises(FencedError):
            zombie.commit({(topic, 0): 5}, fence=["t0", 1, "w0"])
        # the successor drains the whole topic
        records = []
        while True:
            got = successor.poll_nowait(max_records=64)
            if not got:
                break
            records.extend(got)
        assert len(records) == 8
        # a REJOINED worker's fresh members are untouched: eviction
        # fires only on live-list DROP transitions
        await bus.produce(control, {
            "kind": "placement", "epoch": 3,
            "assignment": {"t0": "w1"}, "prev": {"t0": "w1"},
            "workers": ["w0", "w1"]}, key="placement")
        fresh = bus.subscribe(topic, group="t0.rule-processing",
                              owner="w0")
        await bus.produce(control, {
            "kind": "placement", "epoch": 4,
            "assignment": {"t0": "w1"}, "prev": {"t0": "w1"},
            "workers": ["w0", "w1"]}, key="placement")
        assert not fresh.evicted
        # a graceful leave (worker closed its consumers itself) makes
        # the eviction a counted no-op
        fresh.close()
        await bus.produce(control, {
            "kind": "placement", "epoch": 5,
            "assignment": {"t0": "w1"}, "prev": {"t0": "w1"},
            "workers": ["w1"]}, key="placement")
        assert bus.metrics.counter("fleet.members_evicted").value == 1
        successor.close()

    run(main())


def test_wire_subscribe_threads_owner_tag(run):
    """A fleet worker's RemoteEventBus owner-tags every membership it
    registers (fleet/worker_main sets bus.owner), so broker-side
    eviction can attribute members to workers across the wire."""
    from sitewhere_tpu.kernel.bus import EventBus
    from sitewhere_tpu.kernel.wire import BusServer, RemoteEventBus

    async def main():
        bus = EventBus()
        server = BusServer(bus)
        await server.start()
        remote = RemoteEventBus("127.0.0.1", server.port)
        remote.owner = "w7"
        await remote.initialize()
        consumer = remote.subscribe("swx1.tenant.t0.inbound-events",
                                    group="t0.inbound-processing")
        await consumer.poll(max_records=1, timeout=0.05)  # binds the cid
        members = bus._groups["t0.inbound-processing"].members
        assert [m.owner for m in members] == ["w7"]
        # eviction over the wire: the broker closes the member; the
        # remote's next poll finds nothing and its commit is refused
        assert bus.evict_owner("w7") == 1
        assert await consumer.poll(max_records=8, timeout=0.05) == []
        consumer.close()
        await remote.stop()
        await server.stop()

    run(main())
