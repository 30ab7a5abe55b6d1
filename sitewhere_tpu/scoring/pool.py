"""Shared multi-tenant scoring pool: one XLA call scores every tenant.

Config 4 [BASELINE.json]. The per-tenant `ScoringSession` (server.py)
gives each tenant its own compiled functions and its own flush cadence —
right for a handful of big tenants, wasteful for hundreds of small ones
(N kernel launches per window, N compile caches). This pool is the other
operating point [SURVEY.md §7 hard part b]:

- all tenants of one model architecture share a `TenantStack` (stacked
  params, mesh-sharded over the `model` axis) and a `StackedDeviceRing`
  (stacked per-tenant device histories, resident in TPU HBM with the
  same tenant-axis sharding);
- admissions from every tenant land in per-tenant queues; one flusher
  with one admission deadline drains them together;
- each flush uploads only `[T_cap, B]` (device id, value) deltas, runs
  ONE vmapped append+gather+score call, and settles the result off-loop
  (the same pipelined-settle design as the dedicated session: host
  syncs are round-trip-priced, so they run in threads and never block
  dispatch), then fans results back out to each tenant's deliver
  callback.

The pool is keyed by (model name, model config): tenants selecting the
same architecture share a stack regardless of their thresholds (applied
host-side per tenant) or trained params (per-slot slices).

**Cross-tenant megabatching (ROADMAP item 3).** This pool IS the
megabatch dispatch path: `rule-processing: {megabatch: {enabled}}` (or
`InstanceSettings.scoring_megabatch`) routes tenants here even without
`shared: true`, collapsing the event loop's one-jit-dispatch-per-tenant
-per-flush-round cost to ONE stacked dispatch per megabatch — the
continuous-batching serving idiom (PAPERS.md, arXiv 2605.25645) that
makes per-worker throughput a function of hardware, not dispatch
overhead. Shapes stay compile-bounded: the tenant axis is the stack's
pow2 capacity, the batch axis is pow2-bucketed (`batch_buckets`), and
ragged per-tenant batches pad into each tenant's scratch row (the
device-side `valid` mask — padding rows score garbage nobody reads).
`megabatch: {window_ms}` sets the megabatch close deadline and
`{max_tenants}` bounds tenants packed per round. Param hot-swap and
tenant register/unregister replace the stacked pytree (never modify it
— the dispatched jit keeps its own reference) and `_flush_round`
snapshots per-tenant versions at dispatch, so an in-flight megabatch
never observes a torn stack and every settled batch is attributed to
the weights that scored it (`TenantStack.fence` counts the mutations
the fence tests pin). The
settled result fans back out through the per-slot deliver path
(`kernel/egresslane.deliver_scored`, concurrently per tenant), so
at-least-once commit discipline, alert emission, and the fused egress
stage are untouched by the aggregation upstream.
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass, field
from typing import Awaitable, Callable, Optional

import numpy as np

from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch, ScoredBatch
from sitewhere_tpu.kernel.egresslane import deliver_scored
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.kernel.tracing import Tracer
from sitewhere_tpu.parallel.tenant_stack import TenantStack
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.ring import StackedDeviceRing
from sitewhere_tpu.scoring.settle import SETTLE_POOL, DeviceStage, to_host
from sitewhere_tpu.utils.retry import retry_backoff

logger = logging.getLogger(__name__)

Deliver = Callable[[ScoredBatch], Awaitable[None]]


@dataclass(frozen=True)
class PoolConfig:
    batch_buckets: tuple[int, ...] = (256, 1024, 4096)
    batch_window_ms: float = 2.0
    mtype: int = 0
    seed: int = 0
    max_inflight: int = 64
    # per-tenant admission backlog (events) before that tenant's slot
    # reports `backlogged`; 0 → 4 × batch_buckets[-1] (see ScoringConfig)
    backlog_cap: int = 0
    # flush-path score readback dtype (see ScoringConfig.score_dtype)
    score_dtype: str = "float16"
    # sparse anomaly readback (see ScoringConfig.readback): pooled form
    # uses per-tenant thresholds as a runtime [T] vector
    readback: str = "full"
    sparse_k: int = 0
    # megabatch window: how long the flusher holds an open megabatch
    # for more tenants'/events' columns before closing it — the ≤1 ms
    # of batching latency traded for the dispatch-rate collapse.
    # 0 → batch_window_ms (the pool has always batched on a deadline;
    # this knob lets the megabatch close faster or slower than the
    # per-tenant admission window without touching it).
    megabatch_window_ms: float = 0.0
    # tenants packed into one stacked dispatch; 0 = every due tenant.
    # The stack always computes all T_cap rows (vmap is shape-static),
    # so this bounds HOST-side packing work and per-dispatch readback
    # width, not device FLOPs — leftover tenants flush in the
    # immediately following round.
    max_tenants: int = 0
    # adaptive megabatch window (the self-tuning dispatch half of mesh
    # serving): let the LIVE close deadline float in
    # [window_s, WINDOW_SPAN × window_s], keyed to the active-tenant
    # count vs the observed tenants-per-dispatch occupancy — a sparse
    # fleet whose rounds keep closing under-packed earns a wider
    # aggregation window; a dense fleet converges back to the
    # configured floor. `window_s` stays the floor either way, so the
    # configured latency budget is never undercut and a 1-tenant pool
    # never pays tuning it can't use.
    window_auto: bool = True

    @property
    def backlog_events(self) -> int:
        return self.backlog_cap or 4 * self.batch_buckets[-1]

    @property
    def window_s(self) -> float:
        """Effective megabatch close deadline in seconds."""
        return (self.megabatch_window_ms or self.batch_window_ms) / 1e3


@dataclass
class _TenantEntry:
    tenant_id: str
    telemetry: TelemetryStore
    threshold: float
    deliver: Deliver
    # (device_index, value, ts, ingest, ctx, admit_monotonic)
    pending: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                        BatchContext, float]] = field(default_factory=list)
    pending_n: int = 0
    inflight: int = 0          # this tenant's share of in-flight flushes
    # reserved platform tenant (config.RESERVED_TENANT — the fleet
    # forecaster's tenant-0 slot): scores through the same megabatch
    # path but must not count as CUSTOMER traffic in the adaptive
    # window tuner's active-tenant view (its once-per-window cadence
    # would drag occupancy down and widen the window for everyone)
    internal: bool = False


class TenantSlot:
    """Per-tenant handle handed to the rule-processing engine; mirrors the
    `ScoringSession` admission surface so the processor loop treats both
    the same way — including `flush_due`/`flush_nowait`, which delegate
    to the POOL-wide megabatch state: on a busy event loop the consumer
    lanes' turns drive flush rounds exactly as they drive a dedicated
    session's (a lone background flusher task starves behind N
    always-ready consumer loops — measured 5.5 rounds/s vs the lanes'
    ~600 — so the flusher only backstops idle-period deadlines)."""

    def __init__(self, pool: "SharedScoringPool", tenant_id: str):
        self.pool = pool
        self.tenant_id = tenant_id
        self.scored_meter = pool.scored_meter
        self.latency = pool.latency
        # stage decomposition is POOL-wide (all tenants share one flusher
        # and one histogram set), exposed per-slot so pooled and
        # dedicated sinks present the same surface to the bench
        self.stage_admit = pool.stage_admit
        self.stage_batch = pool.stage_batch
        self.stage_device = pool.stage_device
        self.stage_sink = pool.stage_sink

    @property
    def ready(self) -> bool:
        return self.pool.ready

    @property
    def warmup_error(self) -> Optional[Exception]:
        return self.pool.warmup_error

    @property
    def flush_due(self) -> bool:
        return self.pool.flush_due

    def flush_nowait(self) -> bool:
        return self.pool.flush_nowait()

    @property
    def flush_wait_s(self) -> float:
        return self.pool.flush_wait_s

    @property
    def pending_n(self) -> int:
        entry = self.pool.tenants.get(self.tenant_id)
        return entry.pending_n if entry is not None else 0

    @property
    def backlogged(self) -> bool:
        """This tenant's admission backlog is at capacity; its consumer
        must pause polling (backpressure, not post-consume drops).
        At-least-once then holds only within the bus retention window
        (see ScoringSession.backlogged)."""
        return self.pending_n >= self.pool.cfg.backlog_events

    @property
    def inflight(self) -> int:
        entry = self.pool.tenants.get(self.tenant_id)
        return entry.inflight if entry is not None else 0

    @property
    def dispatch_count(self) -> int:
        return self.pool.dispatch_count

    @property
    def settled_count(self) -> int:
        return self.pool.settled_count

    @property
    def settled_through(self) -> int:
        return self.pool.settled_through

    @property
    def idle(self) -> bool:
        """This tenant's commit fast path: nothing of ITS OWN pending or
        in flight (other tenants' load must not starve this tenant's
        offset commits or engine stop)."""
        return self.pending_n == 0 and self.inflight == 0

    async def drain(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        while not self.idle and time.monotonic() < deadline:
            await asyncio.sleep(0.01)

    @property
    def version(self) -> int:
        return self.pool.stack.versions.get(self.tenant_id, 0)

    def admit(self, batch: MeasurementBatch) -> None:
        self.pool.admit(self.tenant_id, batch)

    def admit_columns(self, device_index: np.ndarray, value: np.ndarray,
                      ts: np.ndarray, ctx: BatchContext) -> None:
        self.pool.admit_columns(self.tenant_id, device_index, value, ts, ctx)

    def swap_params(self, params: dict) -> int:
        version = self.pool.stack.set_params(self.tenant_id, params)
        if self.pool.streaming:
            # streaming state (h/c/pred) is a function of the weights —
            # reseed this tenant's rows from its host history, same as
            # ScoringSession.swap_params (reusing the params in hand, not
            # a device→host gather of the slice just written)
            self.pool.reseed(self.tenant_id, params=params)
        return version

    def reload_history(self) -> None:
        """Re-seed this tenant's ring slice from its host store (bulk
        imports that bypassed admit) — mirrors ScoringSession's."""
        self.pool.reseed(self.tenant_id)


class SharedScoringPool:
    """One stack + one ring + one flusher for every tenant of one model
    architecture."""

    def __init__(self, model, metrics: MetricsRegistry,
                 cfg: PoolConfig = PoolConfig(), mesh=None, tracer=None,
                 faults=None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        # a pool built without the runtime's tracer (tests, tools)
        # keeps one of its own: the hot path has one shape
        self.tracer = tracer if tracer is not None else Tracer(
            metrics=metrics)
        # chaos seam (kernel/faults.py "scoring.megabatch"): consulted
        # at admission — the one pool surface reached from inside a
        # consumer loop's per-record quarantine, so an injected fault
        # dead-letters the offending record with provenance instead of
        # crashing the pool's (unsupervised) flusher task
        self.faults = faults
        self.stack = TenantStack(model, mesh=mesh, seed=cfg.seed)
        self.ring: Optional[StackedDeviceRing] = None  # created on first register
        self.tenants: dict[str, _TenantEntry] = {}
        self.ready = True          # flips False while capacity warms up
        # newest warm-up failure, None once a pass succeeds (see
        # ScoringSession.warmup_error)
        self.warmup_error: Optional[Exception] = None
        self.inflight = 0
        self.dispatch_count = 0
        self.settled_count = 0
        self._outstanding: set[int] = set()   # dispatched, not yet settled
        # strong refs to in-flight settle tasks: the loop keeps only
        # weak ones, and a GC'd settle leaves `inflight`/`_outstanding`
        # permanently stuck — the megabatch round never completes again
        self._settle_tasks: set = set()
        self._pending_max = -1     # highest device index waiting
        self._wake = asyncio.Event()
        self._deadline: Optional[float] = None
        self._flusher: Optional[asyncio.Task] = None
        self._warmup: Optional[asyncio.Task] = None
        self._warmed_key: tuple = ()
        self.scored_meter = metrics.meter("scoring.events_scored")
        self.latency = metrics.histogram("scoring.e2e_latency_s")
        self.anomalies = metrics.counter("scoring.anomalies_detected")
        self.anomaly_overflow = metrics.counter("scoring.anomaly_overflow")
        self.flush_rounds = metrics.counter("scoring.pool_flush_rounds")
        self.dropped = metrics.counter("scoring.admissions_dropped")
        self.sink_failures = metrics.counter("scoring.sink_failures")
        # megabatch observability: `scoring.dispatches` is the SAME
        # registry counter the dedicated session incs (instance-wide jit
        # dispatch rate, the A/B's denominator); megabatch_dispatches
        # counts only stacked dispatches; tenants_per_dispatch shows how
        # much cross-tenant aggregation each flush round achieved;
        # stack_rebuilds surfaces capacity growths (each = a recompile
        # round behind the warmup gate)
        self.dispatches = metrics.counter("scoring.dispatches")
        # dispatches whose every take arrived ascending: no host sort
        self.ascending = metrics.counter("scoring.ring.ascending")
        self.megabatch_dispatches = metrics.counter(
            "scoring.megabatch_dispatches")
        self.megabatch_tenants = metrics.histogram(
            "scoring.megabatch_tenants_per_dispatch",
            buckets=[1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0])
        self.stack_rebuilds = metrics.counter("scoring.stack_rebuilds")
        self._rebuilds_seen = 0
        # latency decomposition, pool-wide (same stage semantics as
        # ScoringSession: admit → batch → device → sink, the device
        # stage in the same three parts)
        self.stage_admit = metrics.histogram("scoring.stage_admit_s")
        self.stage_batch = metrics.histogram("scoring.stage_batch_s")
        self.device_stage = DeviceStage(metrics, self.tracer)
        self.stage_device = self.device_stage.total
        self.stage_sink = metrics.histogram("scoring.stage_sink_s")
        # mesh-sharded serving observability: how many devices the
        # stacked dispatch actually spans (0 = single-device), plus the
        # adaptive-window state — the live close deadline and how many
        # times the tuner moved it (the A/B artifact's auto-tuner
        # decision count)
        # per-pool suffix (one pool per model architecture; a shared
        # base name would be last-writer-wins with several pools)
        self.mesh_gauge = metrics.gauge(
            f"scoring.mesh_devices:{model.name}")
        self.mesh_gauge.set(mesh.size if mesh is not None else 0)
        # per-device mesh telemetry (docs/OBSERVABILITY.md fleet
        # observability): tenant-row occupancy of the stacked dispatch
        # and a LIVE per-device model-throughput estimate — sampled by
        # the telemetry beat into every beat/heartbeat, so the standing
        # "read the tflops on a real rig" ask has a live surface
        # instead of only end-of-run bench artifacts
        self.occupancy_gauge = metrics.gauge(
            f"scoring.mesh_row_occupancy:{model.name}")
        self.tflops_gauge = metrics.gauge(
            f"scoring.model_tflops_per_device:{model.name}")
        # EMA over per-dispatch device throughput: one settle's
        # events/(device seconds) is noisy (tiny megabatches, cold
        # shapes) — α=0.2 smooths to ~5 dispatches of memory
        self._tflops_ema = 0.0
        self._window_s = cfg.window_s
        self.window_adjusts = metrics.counter(
            "scoring.megabatch_window_adjusts")
        self.window_gauge = metrics.gauge(
            f"scoring.megabatch_window_ms:{model.name}")
        self.window_gauge.set(self._window_s * 1e3)
        # window-tuner observation state: tenants that ADMITTED since
        # the last evaluation (idle registered tenants must not count
        # — they have no columns a wider window could aggregate) + the
        # packed-tenant sum over the evaluation period
        self._tuner_tenants: set[str] = set()
        self._packed_sum = 0.0
        self._rounds_since_adjust = 0

    @property
    def settled_through(self) -> int:
        """Commit barrier: every dispatch with seq < this has settled."""
        return min(self._outstanding) if self._outstanding else self.dispatch_count

    # -- per-device mesh telemetry ------------------------------------------

    def _note_device_throughput(self, n_events: int,
                                device_s: float) -> None:
        """Fold one settled dispatch into the live per-device tflops
        estimate. Per-dispatch events/(device seconds) overlaps under
        pipelining (inflight > 1), so this is the per-dispatch view —
        the bench's wall-clock number stays the ground truth; this one
        is the always-on gauge a real rig reads between benches."""
        flops_ev = float(getattr(self.model, "flops_per_event",
                                 lambda: 0.0)())
        if device_s <= 0.0 or n_events <= 0 or flops_ev <= 0.0:
            return
        devices = max(self.mesh.size if self.mesh is not None else 1, 1)
        tflops = n_events * flops_ev / device_s / 1e12 / devices
        self._tflops_ema = (tflops if self._tflops_ema == 0.0
                            else 0.8 * self._tflops_ema + 0.2 * tflops)
        self.tflops_gauge.set(round(self._tflops_ema, 6))

    def mesh_stats(self) -> dict:
        """The SPMD dispatch path's live telemetry (beat sample `mesh`
        block, worker heartbeat `signals.mesh`, fleet observer
        occupancy matrix): per-mesh-axis shape, tenant-row occupancy of
        the stacked dispatch, the adaptive window's live deadline, and
        the per-device model-throughput EMA."""
        cap = int(self.stack.capacity)
        rows = len(self.tenants)
        occupancy = round(rows / cap, 4) if cap else 0.0
        self.occupancy_gauge.set(occupancy)
        return {
            "model": self.model.name,
            "devices": int(self.mesh.size) if self.mesh is not None else 0,
            "shape": ({str(k): int(v) for k, v
                       in dict(self.mesh.shape).items()}
                      if self.mesh is not None else {}),
            "tenant_rows": rows,
            "row_capacity": cap,
            "row_occupancy": occupancy,
            "window_ms_live": round(self._window_s * 1e3, 3),
            "dispatches": int(self.dispatch_count),
            "inflight": int(self.inflight),
            "model_tflops_per_device": round(self._tflops_ema, 5),
        }

    # -- registration -------------------------------------------------------

    def register(self, tenant_id: str, telemetry: TelemetryStore,
                 threshold: float, deliver: Deliver,
                 params: Optional[dict] = None,
                 internal: bool = False) -> TenantSlot:
        if tenant_id in self.tenants:
            raise ValueError(f"tenant {tenant_id!r} already registered")
        slot = self.stack.add_tenant(tenant_id, params)
        self.tenants[tenant_id] = _TenantEntry(
            tenant_id, telemetry, threshold, deliver, internal=internal)
        host = telemetry.channels.get(self.cfg.mtype)
        host_cap = host.capacity if host is not None else 1024
        if self.ring is None:
            self.ring = self._new_ring(host_cap)
        else:
            self.ring.ensure(self.stack.capacity, host_cap - 1)
            self.ring.clear_tenant(slot)  # a reused slot must not leak history
        self._seed_tenant_ring(tenant_id, slot, telemetry, params=params)
        self._note_rebuilds()
        self._ensure_started()
        if self._current_key() != self._warmed_key:
            self._start_warmup()
        return TenantSlot(self, tenant_id)

    @property
    def streaming(self) -> bool:
        return bool(getattr(self.model, "streaming", False))

    def _new_ring(self, device_cap: int):
        """Stacked window ring (per-event W-step rescan) or stacked
        streaming ring (one model step per event) — the model declares
        which hot path it wants, exactly like the dedicated session."""
        if self.streaming:
            from sitewhere_tpu.scoring.stream import StackedStreamingRing

            return StackedStreamingRing(
                self.model, self.stack.capacity, device_cap=device_cap,
                mesh=self.mesh, score_dtype=self.cfg.score_dtype,
                sparse=self.cfg.readback == "anomalies",
                sparse_k=self.cfg.sparse_k)
        if self.cfg.readback == "anomalies":
            logger.warning("readback='anomalies' needs a streaming "
                           "model; %s uses the stacked window ring — "
                           "full readback", type(self.model).__name__)
        return StackedDeviceRing(
            self.model.cfg.window, self.stack.capacity,
            device_cap=device_cap, mesh=self.mesh,
            score_dtype=self.cfg.score_dtype)

    def _seed_tenant_ring(self, tenant_id: str, slot: int,
                          telemetry: TelemetryStore,
                          params: Optional[dict] = None) -> None:
        host = telemetry.channels.get(self.cfg.mtype)
        if host is None:
            return
        w = self.model.cfg.window
        x, _ = host.window(np.arange(host.capacity), w)
        cnt = np.minimum(host.count, w)
        if self.streaming:
            # streaming state is a function of this tenant's WEIGHTS —
            # seed by replaying its host windows under its params slice
            if params is None:
                params = self.stack.get_params(tenant_id)
            self.ring.load_tenant(slot, x, cnt, params)
        else:
            self.ring.load_tenant(slot, x, cnt)

    def reseed(self, tenant_id: str, params: Optional[dict] = None) -> None:
        """Re-seed one tenant's ring rows from its host store. The ring
        is sized from the host store at register time, so a store that
        has grown since (a bulk import, a bootstrapped fleet) grows the
        ring here — and the compiled buckets with it: re-warm behind
        the ready gate rather than let the next flush compile on the
        hot path (on a v5e that was a 0.6 s loop stall, long enough for
        the overload controller to reject frames)."""
        self._seed_tenant_ring(tenant_id, self.stack.slots[tenant_id],
                               self.tenants[tenant_id].telemetry,
                               params=params)
        if self._current_key() != self._warmed_key:
            self._start_warmup()

    def unregister(self, tenant_id: str) -> None:
        entry = self.tenants.pop(tenant_id, None)
        slot = self.stack.slots.get(tenant_id)
        if slot is not None and self.ring is not None:
            self.ring.clear_tenant(slot)
        self.stack.remove_tenant(tenant_id)
        if entry is not None and entry.pending_n:
            self.dropped.inc(entry.pending_n)

    def _ensure_started(self) -> None:
        if self._flusher is None or self._flusher.done():
            self._flusher = asyncio.create_task(
                self._run(), name=f"scoring-pool/{self.model.name}")

    # -- warmup -------------------------------------------------------------

    def _current_key(self) -> tuple:
        return (self.stack.capacity,
                self.ring.t_cap if self.ring else 0,
                self.ring.device_cap if self.ring else 0)

    def _start_warmup(self) -> None:
        if self._warmup is not None and not self._warmup.done():
            self._warmup.cancel()
        self.ready = False
        self._warmup = asyncio.create_task(
            self._warm_async(), name=f"scoring-pool/{self.model.name}/warmup")

    async def _warm_async(self) -> None:
        """Compile every batch bucket at the current capacities off the
        hot path; flushes are held (and backlog capped) meanwhile.

        A failure (device fault, OOM at a large bucket) must not stall
        the pool forever: recover the ring and retry with backoff (the
        retry helper keeps recovery inside the protected scope). If the
        capacities grow mid-warmup, the attempt restarts at the new
        shapes until a full pass completes at a stable key."""

        async def attempt():
            while True:
                key = self._current_key()
                # the same data-axis-padded widths the flush rounds
                # dispatch (_bucket_for), so warmup compiles the exact
                # shapes the hot path will hit
                for b in (self.stack.pad_batch(b0)
                          for b0 in self.cfg.batch_buckets):
                    dev = np.tile(self.ring.padding(b), (self.ring.t_cap, 1))
                    v = np.zeros((self.ring.t_cap, b), np.float32)
                    if getattr(self.ring, "sparse", False):
                        out = self.ring.update_and_score(
                            self.model, self.stack.stacked, dev, v,
                            thresholds=self._thresholds())
                    else:
                        out = self.ring.update_and_score(
                            self.model, self.stack.stacked, dev, v)
                    from sitewhere_tpu.scoring.stream import result_ready
                    while not result_ready(out):
                        await asyncio.sleep(0.01)
                    if self._current_key() != key:
                        break  # grew mid-warmup; recompile at new shapes
                else:
                    self._warmed_key = key
                    return

        def failed(exc: Exception) -> None:
            self.warmup_error = exc

        await retry_backoff(
            attempt, lambda: self._recover_ring(restart_warmup=False),
            logger, "pool warmup", on_error=failed)
        self.warmup_error = None
        self.ready = True
        self._wake.set()

    # -- admission ----------------------------------------------------------

    def admit(self, tenant_id: str, batch: MeasurementBatch) -> None:
        entry = self.tenants[tenant_id]
        if self.faults is not None:
            # sync check (admit has no loop to block): a raised fault
            # propagates to the admitting consumer's per-record
            # quarantine — the record dead-letters with provenance and
            # nothing was taken yet, so nothing is lost
            self.faults.check("scoring.megabatch")
            if self.mesh is not None:
                # the mesh-sharded dispatch's own chaos seam: same
                # quarantine contract, armed only when scoring actually
                # rides a device mesh
                self.faults.check("scoring.mesh")
        mask = batch.mtype == self.cfg.mtype
        if mask.all():
            dev, val, ts = batch.device_index, batch.value, batch.ts
        else:
            dev, val, ts = (batch.device_index[mask], batch.value[mask],
                            batch.ts[mask])
        if dev.shape[0] == 0:
            return
        now = time.monotonic()
        self.stage_admit.observe(now - batch.ctx.ingest_monotonic)
        if self.cfg.window_auto and not entry.internal:
            # window tuner: live CUSTOMER traffic (guarded — with the
            # tuner off _tune_window never reaches its periodic clear,
            # and the set would grow without bound under tenant churn;
            # internal slots like tenant-0 admit on their own cadence
            # and must not count as aggregatable load)
            self._tuner_tenants.add(tenant_id)
        ingest = np.full(dev.shape[0], batch.ctx.ingest_monotonic)
        entry.pending.append((dev, val, ts, ingest, batch.ctx, now))
        entry.pending_n += dev.shape[0]
        if dev.shape[0]:
            self._pending_max = max(self._pending_max, int(dev.max()))
        if self._deadline is None:
            # the LIVE window (adaptive when cfg.window_auto): the
            # tuner floats it above the configured floor, never below
            self._deadline = time.monotonic() + self._window_s
        self._wake.set()

    def admit_columns(self, tenant_id: str, device_index: np.ndarray,
                      value: np.ndarray, ts: np.ndarray,
                      ctx: BatchContext) -> None:
        """Column-block admission for the historical replay plane
        (sitewhere_tpu/history): the caller hands scoring columns
        straight out of a decoded cold-tier block — already
        mtype-filtered, so no MeasurementBatch wrapper, no mask pass,
        no admit-stage latency sample (a replayed event's ingest time
        is its original one; measuring "admission delay" against it
        would record hours, not microseconds) and no window-tuner vote
        (replay slots register internal, like tenant-0). Internal-only
        contract: live ingress keeps going through admit()."""
        entry = self.tenants[tenant_id]
        if self.faults is not None:
            # same chaos seams as admit(): a raised fault surfaces in
            # the replay driver before the block is taken
            self.faults.check("scoring.megabatch")
            if self.mesh is not None:
                self.faults.check("scoring.mesh")
        n = device_index.shape[0]
        if n == 0:
            return
        now = time.monotonic()
        entry.pending.append((device_index, value, ts,
                              np.full(n, ctx.ingest_monotonic), ctx, now))
        entry.pending_n += n
        self._pending_max = max(self._pending_max, int(device_index.max()))
        if self._deadline is None:
            self._deadline = time.monotonic() + self._window_s
        self._wake.set()

    # -- flushing -----------------------------------------------------------

    @property
    def _total_pending(self) -> int:
        return sum(e.pending_n for e in self.tenants.values())

    def _note_rebuilds(self) -> None:
        """Publish stack capacity growths since the last look as the
        `scoring.stack_rebuilds` counter (each growth = a bucket
        recompile round behind the warmup gate)."""
        d = self.stack.rebuilds - self._rebuilds_seen
        if d > 0:
            self.stack_rebuilds.inc(d)
            self._rebuilds_seen = self.stack.rebuilds

    def _thresholds(self) -> np.ndarray:
        """Per-slot alert bars for the sparse step ([T_cap] f32);
        empty slots get +inf so they can never report."""
        th = np.full(self.ring.t_cap, np.inf, np.float32)
        for tid, e in self.tenants.items():
            slot = self.stack.slots.get(tid)
            if slot is not None and slot < th.shape[0]:
                th[slot] = e.threshold
        return th

    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.batch_buckets:
            if n <= b:
                return self.stack.pad_batch(b)
        # a data-axis multiple either way: the batch columns shard over
        # the mesh `data` axis, and an uneven split would silently
        # gather the ragged tail onto one device
        return self.stack.pad_batch(self.cfg.batch_buckets[-1])

    # -- adaptive megabatch window (self-tuning dispatch) -------------------

    # widen at most to 8× the configured floor; adjust geometrically, at
    # most once per 16 flush rounds, and only OUTSIDE the [0.5, 0.9]
    # occupancy band — the hysteresis gap that makes the tuner converge
    # instead of flapping between widen and narrow (test-pinned)
    WINDOW_SPAN = 8.0
    WINDOW_ADJUST_EVERY = 16

    def _tune_window(self, packed: int) -> None:
        """Fold one closed megabatch's occupancy into the window tuner:
        every WINDOW_ADJUST_EVERY rounds, compare the mean
        tenants-per-dispatch against the tenants that ACTUALLY admitted
        during the period (`_tuner_tenants`, fed by `admit` — idle
        registered tenants have no columns a wider window could
        aggregate, so they must not drag the occupancy down and pin the
        window at the cap for nothing). Under-packed periods mean the
        window closed before live tenants' columns arrived — widen so
        aggregation (the dispatch-rate collapse) recovers; near-full
        periods mean the window is not the binding constraint — narrow
        back toward the configured floor and give the latency back."""
        if not self.cfg.window_auto:
            return
        self._packed_sum += packed
        self._rounds_since_adjust += 1
        if self._rounds_since_adjust < self.WINDOW_ADJUST_EVERY:
            return
        active = len(self._tuner_tenants)
        if self.cfg.max_tenants:
            active = min(active, self.cfg.max_tenants)
        mean_packed = self._packed_sum / self._rounds_since_adjust
        self._packed_sum = 0.0
        self._rounds_since_adjust = 0
        self._tuner_tenants.clear()
        if active <= 1:
            return  # one live tenant: nothing to aggregate, floor holds
        frac = mean_packed / active
        base = self.cfg.window_s
        if frac < 0.5 and self._window_s < base * self.WINDOW_SPAN:
            self._window_s = min(self._window_s * 1.5,
                                 base * self.WINDOW_SPAN)
        elif frac > 0.9 and self._window_s > base:
            self._window_s = max(self._window_s * 0.67, base)
        else:
            return  # in the hysteresis band (or pinned at a bound): hold
        self.window_adjusts.inc()
        self.window_gauge.set(self._window_s * 1e3)

    @property
    def flush_due(self) -> bool:
        """The megabatch is ready to close: pending work, warmed, under
        the inflight cap, and either the megabatch window expired or
        waiting can no longer improve the pack — i.e. every registered
        tenant (up to the per-round `max_tenants` cap) already has a
        full bucket's take. A total-pending bucket trigger (the first
        cut) closed on ONE tenant's full payload and defeated the
        cross-tenant window entirely: tenants-per-dispatch measured 0.8
        where the whole point is >1 (the continuous-batching semantic:
        hold the batch while it can still grow, never past the
        deadline)."""
        if not self.ready or self._total_pending == 0:
            return False
        if self.inflight >= self.cfg.max_inflight:
            return False  # backpressure: let settles catch up
        if time.monotonic() >= (self._deadline or 0.0):
            return True
        bucket = self.cfg.batch_buckets[-1]
        quota = len(self.tenants)
        if self.cfg.max_tenants:
            quota = min(quota, self.cfg.max_tenants)
        full = sum(1 for e in self.tenants.values()
                   if e.pending_n >= bucket)
        return quota > 0 and full >= quota

    @property
    def flush_wait_s(self) -> float:
        """How long a consumer poll may wait before the megabatch
        deadline (same contract as ScoringSession.flush_wait_s)."""
        if self._total_pending == 0 or not self.ready:
            return 0.2
        if self.inflight >= self.cfg.max_inflight:
            return 0.005
        return max((self._deadline or 0.0) - time.monotonic(), 0.0)

    def flush_nowait(self) -> bool:
        """Close and dispatch the due megabatch NOW (called from the
        consumer lanes' turns, like a session flush; the background
        flusher backstops idle-period deadlines). Returns False when
        nothing was due or a regrow held the round.

        Drains the WHOLE pending backlog — bucket-sized stacked rounds
        back-to-back — matching `ScoringSession.flush_nowait`'s chunked
        drain: the inflight cap gates STARTING a flush, not its rounds.
        A consumer poll can gulp far more than one bucket per tenant
        (256 records × fleet-sized batches); leaving the excess pending
        across turns is how the first cut ballooned slot backlogs until
        the overload controller shed a flood the scorer could absorb."""
        if not self.flush_due:
            return False
        if (self._pending_max >= self.ring.device_cap
                or self.stack.capacity != self.ring.t_cap):
            # a pending event outgrew the ring (or the stack grew):
            # grow + recompile off the hot path; the ready gate holds
            # flushes (and caps the backlog) meanwhile
            self.ring.ensure(self.stack.capacity, self._pending_max)
            self._start_warmup()
            return False
        self._deadline = None
        while self._total_pending > 0:  # no awaits: admission can't race
            self.flush_rounds.inc()
            self._flush_round()
        # a multi-round drain re-arms the deadline for its own leftovers
        # (hot, in the past); clear it so the NEXT admission opens a
        # fresh megabatch window instead of closing instantly unpacked
        self._deadline = None
        return True

    async def _run(self) -> None:
        while True:
            timeout = 0.2
            if self.ready and self._deadline is not None:
                timeout = max(self._deadline - time.monotonic(), 0.0)
            try:
                await asyncio.wait_for(self._wake.wait(), timeout)
            except asyncio.TimeoutError:
                pass
            self._wake.clear()
            if not self.ready or self._total_pending == 0:
                continue
            if self.inflight >= self.cfg.max_inflight:
                await asyncio.sleep(0.005)
                self._wake.set()
                continue
            self.flush_nowait()

    def _flush_round(self) -> None:
        """Close the megabatch: take up to one bucket of rows from every
        due tenant (bounded by `max_tenants` per round), pack them into
        stacked `[T_cap, B]` columns, and dispatch ONE vmapped call per
        occurrence round (events for the same device within a take are
        applied and scored in arrival order, so a coalesced backlog
        scores identically to per-tick flushes), then schedule the
        settle. Leftovers — boundary-batch tails and tenants past the
        per-round cap — re-queue (the wake stays set so the next round
        follows immediately).

        Version fence: per-tenant model versions are snapshotted here,
        at dispatch time, and ride the metas into the settle — a param
        hot-swap or register/unregister landing while this megabatch is
        in flight can never tear the attribution (the dispatched jit
        already holds its own reference to the stacked params it read).
        """
        self._note_rebuilds()
        takes: dict[str, tuple] = {}
        max_t = self.cfg.max_tenants
        for tid, e in self.tenants.items():
            if e.pending_n == 0:
                continue
            if max_t and len(takes) >= max_t:
                # tenants past the per-dispatch bound ride the next
                # round, immediately (wake + hot deadline)
                self._wake.set()
                if self._deadline is None:
                    self._deadline = time.monotonic()
                break
            # take whole admitted batches up to the bucket budget; split
            # only the boundary batch — its tail re-queues WITH ITS OWN
            # ctx (the old concat-then-cut requeued the tail under the
            # last batch's ctx, misattributing tenant/source/trace for
            # every earlier batch's leftover events)
            taken: list[tuple] = []
            traces = []
            budget = self.cfg.batch_buckets[-1]
            now = time.monotonic()
            while e.pending and budget > 0:
                p = e.pending[0]
                n = p[0].shape[0]
                if n <= budget:
                    e.pending.pop(0)
                    taken.append(p)
                    traces.append((p[4].trace_id, n, p[5]))
                    budget -= n
                elif not taken:
                    head = tuple(c[:budget] for c in p[:4]) + (p[4], p[5])
                    e.pending[0] = tuple(c[budget:] for c in p[:4]) \
                        + (p[4], p[5])
                    taken.append(head)
                    traces.append((p[4].trace_id, budget, p[5]))
                    budget = 0
                else:
                    # leftover budget smaller than the next whole batch:
                    # end the take at the batch boundary instead of
                    # shearing it. A sheared head used to drag the
                    # boundary batch's events into this take — for the
                    # replay plane's rank-round chunks that turns two
                    # duplicate-free takes into two dup-bearing ones,
                    # each paying the occurrence split (argsort+unique)
                    # the rounds were packed to avoid. The remainder
                    # keeps its own ctx and leads the next round.
                    break
                self.stage_batch.observe(now - p[5])
            e.pending_n = sum(p[0].shape[0] for p in e.pending)
            if e.pending_n:
                self._wake.set()
                if self._deadline is None:
                    self._deadline = time.monotonic()
            dev = np.concatenate([p[0] for p in taken])
            val = np.concatenate([p[1] for p in taken])
            ts = np.concatenate([p[2] for p in taken])
            ing = np.concatenate([p[3] for p in taken])
            # the take's delivery ctx: exact when one batch, merged
            # sources when several (same convention as the dedicated
            # session's _take_pending)
            sources = {p[4].source for p in taken}
            ctx = taken[0][4] if len(sources) == 1 else BatchContext(
                tenant_id=tid, source="+".join(sorted(sources)),
                ingest_monotonic=min(p[4].ingest_monotonic for p in taken))
            takes[tid] = (dev, val, ts, ing, traces, ctx)
        if self._total_pending == 0:
            self._pending_max = -1
        if not takes:
            return
        t_cap = self.ring.t_cap

        # split every tenant's take into occurrence rounds
        # meta: (tid, slot, n, dev, ts, ing, traces, ev_rounds, ctx,
        #        version-at-dispatch)
        metas = []
        round_parts: list[list[tuple[int, np.ndarray, np.ndarray]]] = []
        ascending = True     # until a take has to be sorted
        for tid, (dev, val, ts, ing, traces, ctx) in takes.items():
            slot = self.stack.slots[tid]
            n = dev.shape[0]
            ev_rounds = []
            # O(n) fast path before the O(n log n) argsort/unique split:
            # a strictly-ascending take (the replay engine's rank-round
            # chunks; a gateway's frame) is one round as it stands. Any
            # other is sorted, for the streaming ring wants every round
            # ascending (scoring/stream.py, "Contract with the
            # engines"): one round still where no id repeats
            if n < 2 or bool((dev[1:] > dev[:-1]).all()):
                parts = [(dev, val, None)]
            else:
                ascending = False
                order = np.argsort(dev, kind="stable")
                sd, sv = dev[order], val[order]
                _, start, cnts = np.unique(sd, return_index=True,
                                           return_counts=True)
                cum = np.arange(n) - np.repeat(start, cnts)
                parts = [(sd[cum == r], sv[cum == r], order[cum == r])
                         for r in range(int(cum.max()) + 1)]
            for r, (rdev, rval, rpos) in enumerate(parts):
                while len(round_parts) <= r:
                    round_parts.append([])
                round_parts[r].append((slot, rdev, rval))
                ev_rounds.append((r, rpos, rdev.shape[0]))
            metas.append((tid, slot, n, dev, ts, ing, traces, ev_rounds,
                          ctx, self.stack.versions.get(tid, 0)))

        t0 = time.monotonic()
        dispatches = []
        try:
            with self.tracer.span(
                    "rule-processing.score.enqueue") as enqueue:
                for parts in round_parts:
                    b = self._bucket_for(max(p[1].shape[0] for p in parts))
                    dev_in = np.tile(self.ring.padding(b), (t_cap, 1))
                    val_in = np.zeros((t_cap, b), np.float32)
                    for slot, rdev, rval in parts:
                        dev_in[slot, :rdev.shape[0]] = rdev
                        val_in[slot, :rdev.shape[0]] = rval
                    if getattr(self.ring, "sparse", False):
                        dispatches.append(self.ring.update_and_score(
                            self.model, self.stack.stacked, dev_in, val_in,
                            thresholds=self._thresholds()))
                    else:
                        dispatches.append(self.ring.update_and_score(
                            self.model, self.stack.stacked, dev_in, val_in))
        except Exception:
            logger.exception("pool dispatch failed; reseeding ring")
            self.dropped.inc(sum(m[2] for m in metas))
            self._recover_ring()
            return
        self.dispatches.inc(len(dispatches))
        if ascending:
            self.ascending.inc()
        self.megabatch_dispatches.inc(len(dispatches))
        self.megabatch_tenants.observe(float(len(metas)))
        self._tune_window(len(metas))
        # dispatch/settle split with megabatch tenant attribution:
        # every packed tenant's traces get a queue-wait span here
        # (its own admit time → this stacked dispatch) and the
        # settle records the shared device half per tenant below
        for tid, _slot, _n, _dev, _ts, _ing, traces, *_ in metas:
            for trace_id, n_ev, t_admit in traces:
                self.tracer.record(trace_id,
                                   "rule-processing.dispatch", tid,
                                   t_admit, max(t0 - t_admit, 0.0),
                                   n_ev)
        self.inflight += 1
        seq = self.dispatch_count
        self.dispatch_count += 1
        self._outstanding.add(seq)
        for tid, *_ in metas:
            e = self.tenants.get(tid)
            if e is not None:
                e.inflight += 1
        task = asyncio.get_running_loop().create_task(
            self._settle_and_deliver(dispatches, metas, t0,
                                     enqueue.t_end, seq),
            name="scoring-settle")
        self._settle_tasks.add(task)
        task.add_done_callback(self._settle_task_done)

    def _settle_task_done(self, task) -> None:
        self._settle_tasks.discard(task)
        if not task.cancelled() and task.exception() is not None:
            # _settle_and_deliver's finally keeps the inflight
            # accounting correct even here, but an escape is a bug —
            # surface it instead of leaving the exception unretrieved
            logger.error("pool settle task died unexpectedly",
                         exc_info=task.exception())

    async def _settle_and_deliver(self, dispatches, metas, t0: float,
                                  t_enq: float,
                                  seq: Optional[int] = None) -> None:
        loop = asyncio.get_running_loop()
        try:
            try:
                reads = await asyncio.gather(*[
                    loop.run_in_executor(SETTLE_POOL, to_host, s)
                    for s in dispatches])
            except BaseException as exc:
                self.dropped.inc(sum(m[2] for m in metas))
                if isinstance(exc, Exception):
                    logger.exception("pool settle failed")
                    return
                raise
            # from here to the sinks the loop itself works (scores
            # scattered back per tenant, thresholds, ScoredBatches): a
            # span, which starts where the device stage's last part ends
            with self.tracer.span("rule-processing.assemble") as assemble:
                now = assemble.t_start
                settled, instants = self.device_stage.observe(
                    reads, t0, t_enq, now)
                deliveries = self._assemble(settled, metas, now, t0)
            for tid, _slot, _n, _dev, _ts, _ing, traces, *_ in metas:
                self.device_stage.record(traces, tid, instants,
                                         assemble.t_end)
            # settle fan-out (kernel/egresslane.py deliver_scored — the
            # ONE delivery contract with the dedicated session): every
            # tenant of the megabatch delivers CONCURRENTLY, failures
            # counted and isolated per tenant, so one tenant's slow or
            # broken sink never holds the rest of the fleet's results
            if deliveries:
                await asyncio.gather(*[
                    deliver_scored(deliver, scored, self.sink_failures,
                                   self.stage_sink, label=f"tenant {tid}")
                    for tid, deliver, scored in deliveries])
        finally:
            self.inflight -= 1
            self.settled_count += 1
            if seq is not None:
                self._outstanding.discard(seq)
            for tid, *_ in metas:
                e = self.tenants.get(tid)
                if e is not None:
                    e.inflight = max(0, e.inflight - 1)

    def _assemble(self, settled, metas, now: float,
                  t0: float) -> list[tuple[str, Deliver, ScoredBatch]]:
        """Settled rounds → one `ScoredBatch` a tenant still registered,
        with the per-tenant accounting."""
        from sitewhere_tpu.scoring.stream import sparse_take

        self._note_device_throughput(sum(m[2] for m in metas), now - t0)
        sparse = bool(settled) and isinstance(settled[0], tuple)
        deliveries: list[tuple[str, Deliver, ScoredBatch]] = []
        for (tid, slot, n, dev, ts, ing, traces, ev_rounds, ctx,
             version) in metas:
            e = self.tenants.get(tid)
            if e is None:  # unregistered mid-flight
                continue
            self.scored_meter.mark(n)
            self.latency.observe_array(now - ing)
            if sparse:
                # per-tenant anomalous subset: remap round-local
                # positions back to this tenant's take positions
                anom_pos: list[np.ndarray] = []
                anom_scores: list[np.ndarray] = []
                for r, rpos, k in ev_rounds:
                    p, v_, overflow = sparse_take(
                        settled[r][0][slot], settled[r][1][slot],
                        settled[r][2][slot], k)
                    if overflow:
                        self.anomaly_overflow.inc(overflow)
                    if p.shape[0] == 0:
                        continue
                    anom_pos.append(p if rpos is None else rpos[p])
                    anom_scores.append(v_)
                if anom_pos:
                    fpos = np.concatenate(anom_pos)
                    a_scores = np.concatenate(anom_scores)
                else:
                    fpos = np.empty(0, np.int64)
                    a_scores = np.empty(0, np.float32)
                self.anomalies.inc(int(fpos.shape[0]))
                scored = ScoredBatch(
                    ctx, dev[fpos], a_scores,
                    np.ones(fpos.shape[0], bool), ts[fpos],
                    # the version snapshotted at DISPATCH, not the
                    # live one: a swap landing mid-flight must not
                    # claim scores the old weights computed
                    model_version=version,
                    total_scored=n)
            else:
                scores = np.empty(n, np.float32)
                for r, rpos, k in ev_rounds:
                    if rpos is None:
                        scores[:k] = settled[r][slot, :k]
                    else:
                        scores[rpos] = settled[r][slot, :k]
                is_anom = scores >= e.threshold
                n_anom = int(is_anom.sum())
                if n_anom:
                    self.anomalies.inc(n_anom)
                scored = ScoredBatch(
                    ctx, dev, scores, is_anom, ts,
                    model_version=version)
            deliveries.append((tid, e.deliver, scored))
        return deliveries

    def _recover_ring(self, restart_warmup: bool = True) -> None:
        self.ring = self._new_ring(
            self.ring.device_cap if self.ring else 1024)
        for tid, entry in self.tenants.items():
            try:
                self._seed_tenant_ring(tid, self.stack.slots[tid],
                                       entry.telemetry)
            except Exception:  # noqa: BLE001 - empty ring still scores
                logger.exception("ring reseed failed for tenant %s", tid)
        if restart_warmup:
            # the fresh ring's compile caches are empty: recompile off the
            # hot path before the next flush (ready gate holds flushes)
            self._warmed_key = ()
            self._start_warmup()

    async def drain(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        while ((self.inflight > 0 or self._total_pending > 0)
               and time.monotonic() < deadline):
            await asyncio.sleep(0.01)

    def close(self) -> None:
        for task in (self._flusher, self._warmup):
            if task is not None and not task.done():
                task.cancel()
        self._flusher = self._warmup = None
        if self.ring is not None:
            self.ring.close()
