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
  through the host side both engines share (scoring/settle.py: the
  occurrence split, the settle threads, score placement, the flight
  book), then fans results back out to each tenant's deliver callback.

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
the fence tests pin). The settled result fans back out to every
tenant's deliver callback concurrently, so at-least-once commit
discipline, alert emission, and the fused egress stage are untouched by
the aggregation upstream.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import time
from dataclasses import dataclass, field
from typing import Awaitable, Callable, Optional

import numpy as np

from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch, ScoredBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.kernel.tracing import Tracer
from sitewhere_tpu.parallel.tenant_stack import TenantStack
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.ring import StackedDeviceRing
from sitewhere_tpu.scoring.settle import (
    SETTLE_POOL,
    Flights,
    booked,
    bucket_for,
    merged_take,
    occurrence_rounds,
)
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
        self.flights = pool.flights

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

    dispatch_count = booked("dispatch_count")
    settled_count = booked("settled_count")
    settled_through = booked("settled_through")

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

    # the flight book's counts, which the consumer's commit barrier reads
    inflight = booked("inflight")
    dispatch_count = booked("dispatch_count")
    settled_count = booked("settled_count")
    settled_through = booked("settled_through")

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
        self.flights = Flights(metrics, self.tracer)
        self._pending_max = -1     # highest device index waiting
        self._wake = asyncio.Event()
        self._deadline: Optional[float] = None
        self._flusher: Optional[asyncio.Task] = None
        self._warmup: Optional[asyncio.Task] = None
        self._warmed_key: tuple = ()
        self.flush_rounds = metrics.counter("scoring.pool_flush_rounds")
        # megabatch observability: megabatch_dispatches counts only
        # stacked dispatches; tenants_per_dispatch shows how much
        # cross-tenant aggregation each flush round achieved;
        # stack_rebuilds surfaces capacity growths (each = a recompile
        # round behind the warmup gate)
        self.megabatch_dispatches = metrics.counter(
            "scoring.megabatch_dispatches")
        self.megabatch_tenants = metrics.histogram(
            "scoring.megabatch_tenants_per_dispatch",
            buckets=[1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0])
        self.stack_rebuilds = metrics.counter("scoring.stack_rebuilds")
        self._rebuilds_seen = 0
        # mesh-sharded serving observability: how many devices the
        # stacked dispatch actually spans (0 = single-device), plus the
        # adaptive-window state — the live close deadline and how many
        # times the tuner moved it
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
            self.flights.dropped.inc(entry.pending_n)

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
        self._check_faults()
        mask = batch.mtype == self.cfg.mtype
        if mask.all():
            dev, val, ts = batch.device_index, batch.value, batch.ts
        else:
            dev, val, ts = (batch.device_index[mask], batch.value[mask],
                            batch.ts[mask])
        if dev.shape[0] == 0:
            return
        self.flights.stage_admit.observe(
            time.monotonic() - batch.ctx.ingest_monotonic)
        if self.cfg.window_auto and not entry.internal:
            # window tuner: live CUSTOMER traffic (guarded — with the
            # tuner off _tune_window never reaches its periodic clear,
            # and the set would grow without bound under tenant churn;
            # internal slots like tenant-0 admit on their own cadence
            # and must not count as aggregatable load)
            self._tuner_tenants.add(tenant_id)
        self._queue(entry, dev, val, ts, batch.ctx)

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
        self._check_faults()
        if device_index.shape[0]:
            self._queue(entry, device_index, value, ts, ctx)

    def _check_faults(self) -> None:
        if self.faults is not None:
            # sync check (admit has no loop to block): a raised fault
            # propagates to the admitting consumer's per-record
            # quarantine (or the replay driver) before anything is
            # taken — the record dead-letters with provenance and
            # nothing is lost
            self.faults.check("scoring.megabatch")
            if self.mesh is not None:
                # the mesh-sharded dispatch's own chaos seam: same
                # quarantine contract, armed only when scoring actually
                # rides a device mesh
                self.faults.check("scoring.mesh")

    def _queue(self, entry: _TenantEntry, dev: np.ndarray, val: np.ndarray,
               ts: np.ndarray, ctx: BatchContext) -> None:
        now = time.monotonic()
        entry.pending.append(
            (dev, val, ts, np.full(dev.shape[0], ctx.ingest_monotonic), ctx,
             now))
        entry.pending_n += dev.shape[0]
        self._pending_max = max(self._pending_max, int(dev.max()))
        if self._deadline is None:
            # the LIVE window (adaptive when cfg.window_auto): the
            # tuner floats it above the configured floor, never below
            self._deadline = now + self._window_s
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
        # a data-axis multiple: the batch columns shard over the mesh
        # `data` axis, and an uneven split would silently gather the
        # ragged tail onto one device
        return self.stack.pad_batch(bucket_for(n, self.cfg.batch_buckets))

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
            budget = self.cfg.batch_buckets[-1]
            now = time.monotonic()
            while e.pending and budget > 0:
                p = e.pending[0]
                n = p[0].shape[0]
                if n <= budget:
                    e.pending.pop(0)
                    taken.append(p)
                    budget -= n
                elif not taken:
                    head = tuple(c[:budget] for c in p[:4]) + (p[4], p[5])
                    e.pending[0] = tuple(c[budget:] for c in p[:4]) \
                        + (p[4], p[5])
                    taken.append(head)
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
                self.flights.stage_batch.observe(now - p[5])
            e.pending_n = sum(p[0].shape[0] for p in e.pending)
            if e.pending_n:
                self._wake.set()
                if self._deadline is None:
                    self._deadline = time.monotonic()
            takes[tid] = merged_take(taken, tid)
        if self._total_pending == 0:
            self._pending_max = -1
        if not takes:
            return
        t_cap = self.ring.t_cap

        # every tenant's take as occurrence rounds; the stack's round r
        # packs each tenant's round r
        # meta: (tid, slot, n, dev, ts, ing, traces, ev_rounds, ctx,
        #        version-at-dispatch)
        metas = []
        round_parts: list[list[tuple[int, np.ndarray, np.ndarray]]] = []
        ascending = True     # until a take has to be sorted
        for tid, (dev, val, ts, ing, ctx, traces) in takes.items():
            slot = self.stack.slots[tid]
            rounds, in_order = occurrence_rounds(dev, val)
            ascending = ascending and in_order
            ev_rounds = []
            for r, (rdev, rval, rpos) in enumerate(rounds):
                if r == len(round_parts):
                    round_parts.append([])
                round_parts[r].append((slot, rdev, rval))
                ev_rounds.append((r, rpos, rdev.shape[0]))
            metas.append((tid, slot, dev.shape[0], dev, ts, ing, traces,
                          ev_rounds, ctx, self.stack.versions.get(tid, 0)))

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
            self.flights.dropped.inc(sum(m[2] for m in metas))
            self._recover_ring()
            return
        self.flights.dispatches.inc(len(dispatches))
        if ascending:
            self.flights.ascending.inc()
        self.megabatch_dispatches.inc(len(dispatches))
        self.megabatch_tenants.observe(float(len(metas)))
        self._tune_window(len(metas))
        for tid, _slot, _n, _dev, _ts, _ing, traces, *_ in metas:
            self.flights.record_dispatch(traces, tid, t0)
        for tid, *_ in metas:
            e = self.tenants.get(tid)
            if e is not None:
                e.inflight += 1
        # SETTLE_POOL is read here, at each launch: replacing this
        # module's name pins the pool's settles to other threads
        self.flights.launch(
            SETTLE_POOL, dispatches, sum(m[2] for m in metas), t0,
            enqueue.t_end, functools.partial(self._assemble, metas, t0),
            release=functools.partial(self._release, metas))

    def _release(self, metas) -> None:
        """A settled megabatch leaves its tenants' in-flight counts."""
        for tid, *_ in metas:
            e = self.tenants.get(tid)
            if e is not None:
                e.inflight = max(0, e.inflight - 1)

    def _assemble(self, metas, t0: float, settled: list,
                  now: float) -> list:
        """Settled rounds → one delivery a tenant of the megabatch
        (scoring/settle.py `Flights.scored`, with the tenant's threshold
        and the version snapshotted at DISPATCH, not the live one: a
        swap landing mid-flight must not claim scores the old weights
        computed). A tenant unregistered mid-flight keeps its spans and
        gets no batch."""
        self._note_device_throughput(sum(m[2] for m in metas), now - t0)
        sparse = isinstance(settled[0], tuple)
        deliveries = []
        for (tid, slot, _n, dev, ts, ing, traces, ev_rounds, ctx,
             version) in metas:
            e = self.tenants.get(tid)
            if e is None:
                deliveries.append((tid, traces, None, None))
                continue
            rounds = [(tuple(part[slot] for part in settled[r]) if sparse
                       else settled[r][slot], k, rpos)
                      for r, rpos, k in ev_rounds]
            deliveries.append((tid, traces, e.deliver, self.flights.scored(
                ctx, dev, ts, ing, now, rounds, e.threshold, version)))
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
