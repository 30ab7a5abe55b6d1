"""Service runtime: services, per-tenant engines, and the instance runtime.

Capability parity with SiteWhere's microservice kernel
(`Microservice`, `MultitenantMicroservice`, `MicroserviceTenantEngine`,
`TenantEngineManager` — [SURVEY.md §2.1, §3.1, §3.5]):

- a `Service` is one logical microservice (device-management,
  inbound-processing, ...) with a lifecycle and an API object other
  services can call;
- a multitenant `Service` hosts one `TenantEngine` per tenant, spun
  up/down in response to tenant-model-update records on the instance bus
  (the reference broadcast the same way over Kafka, §3.5);
- a `ServiceRuntime` is the whole instance: the bus, topic naming, metrics,
  and the set of services. In the reference each service is a separate JVM
  on k8s; here they share one process/event-loop by default, which is what
  collapses the reference's four broker hops on the scoring path
  [SURVEY.md §3.2 hot-loop note] while keeping topics observable.

Cross-service calls: the reference goes through gRPC `ApiChannel`s with
wait-for-available retry [SURVEY.md §2.1 "gRPC plumbing"]. Here
`ServiceRuntime.api(identifier)` returns the target service's API object
directly, and `wait_for_api(identifier)` gives the same
wait-until-available semantics for startup ordering.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Any, Optional

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.kernel.bus import EventBus, FencedError, TopicNaming
from sitewhere_tpu.kernel.lifecycle import (
    BackgroundTaskComponent,
    LifecycleComponent,
    LifecycleProgressMonitor,
    LifecycleStatus,
)
from sitewhere_tpu.kernel.metrics import MetricsRegistry

logger = logging.getLogger(__name__)


class FenceState:
    """Worker-side fencing ledger (one per ServiceRuntime).

    A fleet worker's `FleetWorker` grants a `(tenant, epoch)` pair here
    when it adopts a tenant and revokes it on release; every data-path
    produce/commit the tenant's engines issue threads the resulting
    `[tenant, epoch, worker]` token (the FEN01 lint contract), and the
    broker's `FenceAuthority` validates it against the live placement.
    On a rejection — synchronous FencedError or the wire client's
    background `on_fenced` callback — `mark_fenced` records the loss and
    notifies the worker, whose apply loop stops the tenant's engines
    WITHOUT publishing a release (the fence already transferred
    ownership; a zombie's release record would carry a stale epoch).

    Non-fleet runtimes never grant anything, so `token()` is None and
    every write stays unfenced (backward compatible by construction)."""

    def __init__(self) -> None:
        self.worker_id: Optional[str] = None
        self._epochs: dict[str, int] = {}
        self.lost: set[str] = set()
        self.on_lost = None       # callback(tenant_id), set by FleetWorker

    def grant(self, tenant_id: str, epoch: int) -> None:
        self._epochs[tenant_id] = int(epoch)
        self.lost.discard(tenant_id)

    def revoke(self, tenant_id: str) -> None:
        self._epochs.pop(tenant_id, None)
        self.lost.discard(tenant_id)

    def epoch(self, tenant_id: str) -> Optional[int]:
        return self._epochs.get(tenant_id)

    def token(self, tenant_id: str):
        epoch = self._epochs.get(tenant_id)
        if epoch is None or self.worker_id is None:
            return None
        return [tenant_id, epoch, self.worker_id]

    def mark_fenced(self, tenant_id: Optional[str],
                    epoch: Optional[int] = None) -> None:
        """A broker rejected this process's write for `tenant_id`: we
        are no longer the owner. Idempotent; safe from sync paths.
        `epoch` is the REJECTED token's epoch when known (async wire
        rejections): a rejection for an OLDER grant than the one we
        currently hold is stale — the tenant was legitimately
        re-adopted since, and fencing the fresh grant would wedge it
        (no release published, no new epoch coming)."""
        if not tenant_id or tenant_id not in self._epochs \
                or tenant_id in self.lost:
            return
        current = self._epochs.get(tenant_id)
        if epoch is not None and current is not None and epoch < current:
            logger.info(
                "fence: ignoring stale rejection for tenant %s (token "
                "epoch %s < current grant %s)", tenant_id, epoch, current)
            return
        self.lost.add(tenant_id)
        # rejections are COUNTED broker-side only (`fence.rejections`,
        # EventBus.check_fence) — counting the worker-side demotion
        # under the same name would conflate per-write rejections with
        # once-per-tenant losses and double-count shared-registry
        # topologies
        logger.warning(
            "fence: data-path write for tenant %s REJECTED (epoch %s, "
            "worker %s) — ownership moved; stopping engines, not "
            "retrying", tenant_id, self._epochs.get(tenant_id),
            self.worker_id)
        if self.on_lost is not None:
            self.on_lost(tenant_id)


class TenantFence:
    """Per-tenant fencing handle data-path helpers thread around
    (`checkpoint_commit` takes one): `token()` resolves the LIVE token
    at call time, `lost()` reports a broker rejection back."""

    __slots__ = ("_state", "_tenant")

    def __init__(self, state: FenceState, tenant_id: str):
        self._state = state
        self._tenant = tenant_id

    def token(self):
        return self._state.token(self._tenant)

    def lost(self) -> None:
        self._state.mark_fenced(self._tenant)


class TenantEngine(LifecycleComponent):
    """Per-tenant engine inside a service (reference: MicroserviceTenantEngine)."""

    def __init__(self, service: "Service", tenant: TenantConfig):
        super().__init__(f"tenant-{tenant.tenant_id}")
        self.service = service
        self.tenant = tenant
        self._fence: Optional[TenantFence] = None

    @property
    def runtime(self) -> "ServiceRuntime":
        return self.service.runtime

    # -- epoch fencing (docs/FLEET.md) --------------------------------------

    @property
    def fence(self) -> TenantFence:
        """This tenant's fencing handle (for `checkpoint_commit`)."""
        if self._fence is None:
            self._fence = TenantFence(self.runtime.fence, self.tenant_id)
        return self._fence

    def fence_token(self):
        """The live `[tenant, epoch, worker]` data-path token — None on
        non-fleet runtimes, so unfenced writes stay unfenced."""
        return self.runtime.fence.token(self.tenant_id)

    def fence_lost(self) -> None:
        """Report a synchronous FencedError: this worker lost the
        tenant; the fleet worker's apply loop stops the engines."""
        self.runtime.fence.mark_fenced(self.tenant_id)

    @property
    def tenant_id(self) -> str:
        return self.tenant.tenant_id

    def tenant_topic(self, function: str) -> str:
        return self.runtime.naming.tenant_topic(self.tenant_id, function)

    @property
    def dead_letter_topic(self) -> str:
        return self.tenant_topic(TopicNaming.DEAD_LETTER)

    async def dead_letter(self, record, exc: BaseException,
                          stage: str) -> None:
        """Quarantine a poison record to this tenant's dead-letter
        topic with provenance (kernel/dlq.py) — the per-record catch
        every consuming loop routes through. Never raises.

        FencedError is NOT poison: the record is fine, THIS WORKER lost
        the tenant (epoch fencing, docs/FLEET.md). Quarantining it would
        both pollute the DLQ and commit past a record the new owner must
        redeliver — instead the loss is recorded and the fleet worker
        stops the engines; the record stays uncommitted for the owner."""
        from sitewhere_tpu.kernel.dlq import quarantine

        if isinstance(exc, FencedError):
            self.fence_lost()
            return
        # the DLQ rate feeds the tenant's overload pressure: a poison
        # storm escalates shedding even before the scorer backlog builds
        self.runtime.flow.note_dead_letter(self.tenant_id)
        await quarantine(self.runtime.bus, self.dead_letter_topic, record,
                         exc, stage, metrics=self.runtime.metrics,
                         tenant_id=self.tenant_id,
                         tracer=self.runtime.tracer,
                         fence=self.fence_token())


class Service(LifecycleComponent):
    """One logical microservice (reference: ConfigurableMicroservice).

    Subclasses set `identifier` and either override the lifecycle hooks
    directly (global services) or implement `create_tenant_engine()`
    (multitenant services; a `TenantEngineManager` child is attached
    automatically when `multitenant=True`).
    """

    identifier: str = "service"
    multitenant: bool = False

    def __init__(self, runtime: "ServiceRuntime"):
        super().__init__(self.identifier)
        self.runtime = runtime
        self.engines: dict[str, TenantEngine] = {}
        if self.multitenant:
            self.engine_manager = TenantEngineManager(self)
            self.add_child(self.engine_manager)

    # -- tenant engines ----------------------------------------------------

    def create_tenant_engine(self, tenant: TenantConfig) -> TenantEngine:
        raise NotImplementedError(f"{self.identifier} is not multitenant")

    def engine(self, tenant_id: str) -> TenantEngine:
        try:
            return self.engines[tenant_id]
        except KeyError:
            raise KeyError(
                f"{self.identifier}: no engine for tenant {tenant_id!r} "
                f"(known: {sorted(self.engines)})") from None

    async def start_tenant_engine(self, tenant: TenantConfig) -> TenantEngine:
        existing = self.engines.get(tenant.tenant_id)
        if existing is not None:
            if (existing.tenant.equivalent(tenant)
                    and existing.status == LifecycleStatus.STARTED):
                # already built from equivalent config: the manager's
                # bootstrap scan and the tenant-model-updates broadcast
                # race on a freshly added tenant (and wire-bus broadcasts
                # decode to copies) — creating twice would needlessly
                # tear down a just-started engine and its state
                return existing
            await existing.stop()
        engine = self.create_tenant_engine(tenant)
        self.engines[tenant.tenant_id] = engine
        await engine.initialize()
        await engine.start()
        return engine

    async def stop_tenant_engine(self, tenant_id: str) -> None:
        engine = self.engines.pop(tenant_id, None)
        if engine is not None:
            await engine.stop()

    def state_tree(self) -> dict:
        """Include tenant engines: they are dict-managed (spun by the
        engine manager), not lifecycle children, but a crashed or
        budget-exhausted loop inside one MUST show in health."""
        out = super().state_tree()
        out["children"].extend(
            e.state_tree() for _, e in sorted(self.engines.items()))
        return out

    # -- convenience -------------------------------------------------------

    @property
    def bus(self) -> EventBus:
        return self.runtime.bus

    @property
    def naming(self) -> TopicNaming:
        return self.runtime.naming

    @property
    def metrics(self) -> MetricsRegistry:
        return self.runtime.metrics

    def api(self) -> Any:
        """The object other services call (override where applicable)."""
        return self


class TenantEngineManager(BackgroundTaskComponent):
    """Watches tenant-model-updates and spins engines (reference: §3.5).

    Records on the instance topic look like
    `{"action": "created"|"updated"|"deleted", "tenant": TenantConfig}`.
    """

    def __init__(self, service: Service):
        super().__init__("tenant-engine-manager")
        self.service = service

    async def _run(self) -> None:
        runtime = self.service.runtime
        if getattr(runtime.settings, "fleet_managed", False):
            # fleet worker runtime: engine ownership is decided by fleet
            # placement records (sitewhere_tpu/fleet), applied through
            # ServiceRuntime.adopt_tenant/release_tenant — reacting to
            # tenant-model-update broadcasts here would make EVERY
            # worker host EVERY tenant and un-shard the fleet
            return
        consumer = runtime.bus.subscribe(
            runtime.naming.instance_topic(TopicNaming.TENANT_MODEL_UPDATES),
            group=f"{self.service.identifier}.tenant-engines",
            name=f"{self.service.identifier}.tenant-engines")
        try:
            # bootstrap tenants already known to the runtime
            for tenant in runtime.tenants.values():
                if tenant.tenant_id not in self.service.engines:
                    await self.service.start_tenant_engine(tenant)
            while True:
                # control topic: instance-level records have no tenant
                # DLQ to quarantine to — malformed updates are counted
                # and skipped instead (per-record isolation either way)
                for record in await consumer.poll(timeout=0.5):  # swxlint: disable=DLQ01
                    try:
                        update = record.value
                        action, tenant = update["action"], update["tenant"]
                    except (TypeError, KeyError) as exc:
                        # a malformed broadcast must not crash the
                        # manager (and re-crash it on every supervised
                        # restart until the budget drains)
                        logger.warning(
                            "%s: malformed tenant-model update %r: %s",
                            self.service.identifier, record.value, exc)
                        runtime.metrics.counter(
                            "tenant_updates.malformed").inc()
                        continue
                    # a wrong-typed `tenant` (e.g. a bare id string) has
                    # both keys and passes the guard above — resolve the
                    # label once, safely, so the isolation handler below
                    # can't itself raise on `tenant.tenant_id` and
                    # restart-loop the manager on the same record
                    tid = getattr(tenant, "tenant_id", tenant)
                    try:
                        if action in ("created", "updated"):
                            await self.service.start_tenant_engine(tenant)
                        elif action == "deleted":
                            await self.service.stop_tenant_engine(tid)
                    except Exception:  # noqa: BLE001 - engine error is isolated
                        logger.exception("%s: tenant %s %s failed",
                                         self.service.identifier, tid, action)
                consumer.commit()
        finally:
            consumer.close()

    async def _do_stop(self, monitor: LifecycleProgressMonitor) -> None:
        await super()._do_stop(monitor)
        for tenant_id in list(self.service.engines):
            await self.service.stop_tenant_engine(tenant_id)


class ServiceRuntime(LifecycleComponent):
    """The whole instance: bus + services + tenants (reference: an
    instance's set of microservices plus its Kafka cluster)."""

    def __init__(self, settings: Optional[InstanceSettings] = None,
                 bus: Optional[Any] = None):
        settings = settings or InstanceSettings()
        super().__init__(f"instance-{settings.instance_id}")
        self.settings = settings
        self.naming = TopicNaming(settings.instance_id)
        self.metrics = MetricsRegistry()
        from sitewhere_tpu.kernel.tracing import Tracer
        self.tracer = Tracer(sample=settings.trace_sample,
                             metrics=self.metrics,
                             stall_s=settings.observe_stall_ms / 1e3)
        # `bus` may be a RemoteEventBus (kernel/wire.py): this process
        # then shares one broker's topics with peer processes — the
        # process-split deployment the reference runs as 14 JVMs
        self.bus = bus if bus is not None else EventBus(
            default_partitions=settings.bus_default_partitions,
            retention=settings.bus_retention)
        if isinstance(self.bus, LifecycleComponent):
            if self.bus.parent is None:
                self.add_child(self.bus)
                # the owning runtime's registry counts broker-side
                # fenced rejections (`fence.rejections`)
                if hasattr(self.bus, "metrics"):
                    self.bus.metrics = self.metrics
            # else: an in-proc bus another runtime already owns (the
            # in-proc fleet topology: N runtimes share one bus) — use
            # it, leave its lifecycle to the owning runtime
        else:
            self._external_bus = self.bus
            if hasattr(self.bus, "metrics"):
                # wire bus: the fast path's gauges/counters
                # (wire.prefetch_credit / linger_batches /
                # frames_coalesced) land on this runtime's registry
                self.bus.metrics = self.metrics
        # epoch fencing, worker side (docs/FLEET.md): the ledger of
        # (tenant, epoch) grants this process holds. FleetWorker sets
        # worker_id/on_lost; non-fleet runtimes never grant, so every
        # token resolves to None and writes stay unfenced.
        self.fence = FenceState()
        if hasattr(self.bus, "on_fenced"):
            # wire bus: a fire-and-forget commit/produce rejection
            # surfaces through the client callback instead of a raise
            self.bus.on_fenced = self.fence.mark_fenced
        if hasattr(self.bus, "tracer"):
            # wire bus: the broker hop records wire.produce/wire.poll
            # spans for traced batches (kernel/wire.py), so a split
            # deployment's trace spine covers the hop between processes
            self.bus.tracer = self.tracer
        # per-tenant flow control (kernel/flow.py): quotas, weighted-fair
        # inbound admission, overload shedding — every ingress edge and
        # the rule-processing shed path consult this
        from sitewhere_tpu.kernel.flow import FlowController
        self.flow = FlowController(settings, self.metrics)
        # pipeline flight recorder (kernel/observe.py): the always-on
        # telemetry beat — event-loop lag probe, consumer-group lag,
        # egress backlog, scoring occupancy, flow mode — sampled into a
        # bounded ring + the metrics registry. A lifecycle child, so it
        # rides the runtime's start/stop and the supervisor's restart
        # budget like every service loop.
        self.beat = None
        if getattr(settings, "observe_enabled", True):
            from sitewhere_tpu.kernel.observe import TelemetryBeat
            self.beat = TelemetryBeat(self)
            self.add_child(self.beat)
        self.services: dict[str, Service] = {}
        self.remotes: dict[str, Any] = {}   # identifier -> RemoteService
        # fleet control plane handle (sitewhere_tpu/fleet): the
        # FleetController registers itself here on the runtime that
        # hosts it, so REST (`GET /api/fleet`) and the observe report
        # can surface placement without a service dependency
        self.fleet = None
        # fleet observability plane (fleet/observer.py): the
        # FleetObserver registers itself here on the broker host —
        # `GET /api/fleet/observe` / `swx top --fleet`
        self.fleet_observer = None
        # durable telemetry history (persistence/durable.py): windowed
        # per-tenant signal series under <data_dir>/telemetry — the
        # beat appends every sample's signals; readback is the
        # train-from-history substrate (ROADMAP item 2)
        self.history = None
        if settings.data_dir and getattr(settings, "observe_history",
                                         True):
            import os as _os

            from sitewhere_tpu.persistence.durable import TelemetryHistory
            self.history = TelemetryHistory(
                _os.path.join(settings.data_dir, "telemetry"),
                window_s=getattr(settings, "observe_history_window_s",
                                 10.0),
                metrics=self.metrics)
        self.tenants: dict[str, TenantConfig] = {}
        # chaos seam: a FaultInjector (kernel/faults.py) installed via
        # install_faults(); None in production — every consulted site
        # guards with one `is not None` test
        self.faults = None
        # monotonic change counter over the tenant-config map — the
        # instance snapshotter's debounce epoch (a size-based epoch
        # aliases: delete bumps a counter while the size drops)
        self.tenant_epoch = 0

    # -- wiring ------------------------------------------------------------

    def add_service(self, service: Service) -> Service:
        if service.identifier in self.services:
            raise ValueError(f"duplicate service {service.identifier}")
        self.services[service.identifier] = service
        self.add_child(service)
        return service

    def add_remote_service(self, identifier: str, host: str, port: int,
                           secret: Optional[str] = None) -> Any:
        """Register a peer process's service: `api(identifier)` and
        `wait_for_engine` resolve to wire proxies (kernel/wire.py)."""
        from sitewhere_tpu.kernel.wire import ApiChannel, RemoteService

        remote = RemoteService(identifier, ApiChannel(host, port,
                                                      secret=secret))
        self.remotes[identifier] = remote
        return remote

    def install_faults(self, injector: Any) -> Any:
        """Install a FaultInjector on the runtime and its bus (chaos
        tests, tests/test_robustness.py). Install BEFORE tenants are added:
        engines capture the injector when they build their durable logs
        and scoring sessions. Returns the injector (chainable)."""
        self.faults = injector
        if hasattr(self.bus, "faults"):
            self.bus.faults = injector
        self.flow.faults = injector
        return injector

    def api(self, identifier: str) -> Any:
        """In-proc equivalent of a gRPC ApiChannel to `identifier`."""
        svc = self.services.get(identifier)
        if svc is not None:
            return svc.api()
        return self.remotes[identifier].api()

    async def wait_for_api(self, identifier: str, timeout: float = 10.0) -> Any:
        """Wait-for-available retry (reference: ApiChannel.waitForApiAvailable)."""
        deadline = asyncio.get_event_loop().time() + timeout
        while True:
            svc = self.services.get(identifier)
            if svc is not None and svc.status == LifecycleStatus.STARTED:
                return svc.api()
            if asyncio.get_event_loop().time() > deadline:
                raise TimeoutError(f"api {identifier} not available after {timeout}s")
            await asyncio.sleep(0.01)

    async def wait_for_engine(self, identifier: str, tenant_id: str,
                              timeout: float = 10.0) -> TenantEngine:
        """Wait until `identifier`'s engine for `tenant_id` is STARTED.

        Tenant-model-update broadcasts reach each service's engine manager
        independently (reference: Kafka consumer groups, §3.5), so engine
        start order across services is scheduler timing — consumers that
        need a peer's engine must wait, exactly like the reference's
        ApiChannel wait-for-available."""
        remote = self.remotes.get(identifier)
        if remote is not None and identifier not in self.services:
            return await remote.wait_engine(tenant_id, timeout=timeout)
        deadline = asyncio.get_event_loop().time() + timeout
        while True:
            svc = self.services.get(identifier)
            if svc is not None:
                eng = svc.engines.get(tenant_id)
                if eng is not None and eng.status == LifecycleStatus.STARTED:
                    return eng
            if asyncio.get_event_loop().time() > deadline:
                raise TimeoutError(
                    f"{identifier} engine for tenant {tenant_id!r} "
                    f"not available after {timeout}s")
            await asyncio.sleep(0.01)

    # -- tenants -----------------------------------------------------------

    async def add_tenant(self, tenant: TenantConfig, *, timeout: float = 60.0) -> None:
        """Register a tenant and broadcast creation (reference: §3.5)."""
        from sitewhere_tpu.config import RESERVED_TENANT

        if tenant.tenant_id == RESERVED_TENANT:
            # the platform's own internal tenant (the fleet forecaster's
            # tenant-0 scoring slot, fleet/forecast.py): it must never
            # become a CUSTOMER tenant — placed on workers, counted in
            # the lag matrix, admitted through the fair roster
            raise ValueError(
                f"tenant id {RESERVED_TENANT!r} is reserved for the "
                "platform's internal scoring slot")
        self.tenants[tenant.tenant_id] = tenant
        self.flow.configure_tenant(tenant)
        self.tenant_epoch += 1
        if self.fleet is not None:
            # this process hosts the fleet control plane: tenant CRUD
            # IS the placement roster (REST create/update included)
            self.fleet.add_tenant(tenant)
        await self.bus.produce(
            self.naming.instance_topic(TopicNaming.TENANT_MODEL_UPDATES),
            {"action": "created", "tenant": tenant}, key=tenant.tenant_id)
        await self._await_engines(tenant.tenant_id, timeout=timeout)

    async def update_tenant(self, tenant: TenantConfig) -> None:
        self.tenants[tenant.tenant_id] = tenant
        self.flow.configure_tenant(tenant)
        self.tenant_epoch += 1
        if self.fleet is not None:
            self.fleet.add_tenant(tenant)
        await self.bus.produce(
            self.naming.instance_topic(TopicNaming.TENANT_MODEL_UPDATES),
            {"action": "updated", "tenant": tenant}, key=tenant.tenant_id)
        await self._await_engines(tenant.tenant_id)

    async def remove_tenant(self, tenant_id: str) -> None:
        tenant = self.tenants.pop(tenant_id, None)
        if tenant is None:
            return
        self.flow.drop_tenant(tenant_id)
        self.tenant_epoch += 1
        if self.fleet is not None:
            self.fleet.remove_tenant(tenant_id)
        await self.bus.produce(
            self.naming.instance_topic(TopicNaming.TENANT_MODEL_UPDATES),
            {"action": "deleted", "tenant": tenant}, key=tenant_id)
        await self._await_engines(tenant_id, present=False)

    async def _await_engines(self, tenant_id: str, *, present: bool = True,
                             timeout: Optional[float] = None) -> None:
        """Block until every multitenant service has (or drops) the engine.

        Default bound comes from `InstanceSettings.engine_ready_timeout_s`
        (generous: engine start may include warm-up compiles on a cold
        cache)."""
        if timeout is None:
            timeout = self.settings.engine_ready_timeout_s
        deadline = asyncio.get_event_loop().time() + timeout
        multitenant = [s for s in self.services.values()
                       if s.multitenant and s.status == LifecycleStatus.STARTED]
        while True:
            current = self.tenants.get(tenant_id)

            def ready(s: Service) -> bool:
                eng = s.engines.get(tenant_id)
                if present:
                    # engine must be running *and* built from equivalent
                    # config (update spins a fresh engine, §3.5; equality
                    # is semantic — wire broadcasts decode to copies)
                    return (eng is not None
                            and eng.status == LifecycleStatus.STARTED
                            and current is not None
                            and eng.tenant.equivalent(current))
                return eng is None
            if all(ready(s) for s in multitenant):
                return
            if asyncio.get_event_loop().time() > deadline:
                lagging = [s.identifier for s in multitenant if not ready(s)]
                raise TimeoutError(
                    f"tenant {tenant_id} engines not {'ready' if present else 'removed'}"
                    f" in {timeout}s: {lagging}")
            await asyncio.sleep(0.005)

    # -- fleet shard ownership (sitewhere_tpu/fleet) -------------------------

    async def adopt_tenant(self, tenant: TenantConfig) -> None:
        """Shard-scoped tenant spin-up: start this runtime's engines for
        `tenant` WITHOUT the instance-wide broadcast. The fleet worker
        calls this when placement assigns it a tenant; the engines join
        the tenant's consumer groups on the shared bus and resume from
        committed offsets (at-least-once across the handoff). Idempotent
        for an equivalent config; a changed config respins the engines
        (start_tenant_engine's equivalence guard)."""
        self.tenants[tenant.tenant_id] = tenant
        self.flow.configure_tenant(tenant)
        self.tenant_epoch += 1
        for service in self.services.values():
            if service.multitenant \
                    and service.status == LifecycleStatus.STARTED:
                await service.start_tenant_engine(tenant)

    async def release_tenant(self, tenant_id: str) -> None:
        """Shard-scoped tenant drain: stop this runtime's engines for
        the tenant (reverse service order — consumers drain, settle
        barriers commit through, offsets persist in the shared group)
        without broadcasting a delete. After this returns, no loop in
        this process consumes the tenant's topics — the new owner may
        safely resume from the committed offsets."""
        if self.tenants.pop(tenant_id, None) is None:
            return
        self.flow.drop_tenant(tenant_id)
        self.tenant_epoch += 1
        for service in reversed(list(self.services.values())):
            if service.multitenant:
                await service.stop_tenant_engine(tenant_id)

    # -- external (wire) bus lifecycle --------------------------------------

    async def _do_initialize(self, monitor: LifecycleProgressMonitor) -> None:
        eb = getattr(self, "_external_bus", None)
        if eb is not None:
            await eb.initialize()

    async def _do_start(self, monitor: LifecycleProgressMonitor) -> None:
        # the collector's pauses, as `busy.gc` and on a profiler trace
        self.tracer.watch_gc()
        # the serving loop's own account: every task step from here on
        # credited to an operator, the selector's wait counted as idle
        self.tracer.watch_loop(asyncio.get_running_loop())

    async def _do_stop(self, monitor: LifecycleProgressMonitor) -> None:
        self.tracer.unwatch_loop()
        self.tracer.unwatch_gc()
        eb = getattr(self, "_external_bus", None)
        if eb is not None:
            await eb.stop()
        for remote in self.remotes.values():
            remote.channel.close()
        if self.history is not None:
            # flush the open telemetry windows to disk (the readback
            # across a restart is the whole point of the tier)
            self.history.close()

    def health(self) -> dict:
        return self.state_tree()
