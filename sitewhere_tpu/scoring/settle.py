"""The host side both scoring engines share (`ScoringSession`,
`SharedScoringPool`) from a take to its delivery: the take's occurrence
rounds, the settle, score placement, the flight book (`Flights`) and its
metrics.

A device→host readback blocks its caller until the device has finished
(how long on the chip's own host: not measured) but releases the GIL and
parallelizes across threads, so every engine settles on a pool of
threads (`SETTLE_POOL`) instead of blocking the event loop. Each engine
module names the pool itself and hands it to every launch, so that a
caller who replaces that module's name pins its settles to other threads.

And the one place that says what the device stage is made of. A
dispatched chunk's `scoring.stage_device_s` runs from `t0` (before its
dispatch) to the instant its settle resumes on the loop; `DeviceStage`
cuts that interval at two instants into three parts that tile it:

    t0 ── enqueue ── t_enq ── device ── t_held ── wake ── now

    enqueue  rounds split, padded, the jit call, read-back started: the
             loop thread's own work (`Tracer.span`, so `busy.`-counted)
    device   wait behind earlier steps + the step + the device→host
             copy + a settle thread's pick-up, until a thread HOLDS the
             bytes (the latest of the chunk's rounds). `to_host` is the
             thread's blocking part of it, annotated
             `rule-processing.score.readback`
    wake     the thread's return → the event loop resumes the task
"""

import asyncio
import ctypes
import logging
import threading
import time
from concurrent.futures import Executor, ThreadPoolExecutor
from typing import Callable, Iterable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from sitewhere_tpu.domain.batch import BatchContext, ScoredBatch
from sitewhere_tpu.kernel.egresslane import deliver_scored
from sitewhere_tpu.kernel.metrics import QUARTER_OCTAVES, MetricsRegistry
from sitewhere_tpu.kernel.tracing import Tracer
from sitewhere_tpu.scoring.stream import result_to_host, sparse_take

logger = logging.getLogger(__name__)


def _name_os_thread() -> None:
    """Give a worker's OS thread its Python name (15 bytes of it): a
    profile's thread lines, `top -H` and a debugger label a thread by
    its OS name, and this interpreter leaves every one of them `python`.
    Linux only; anywhere else the lines keep the name they had."""
    try:
        prctl = ctypes.CDLL(None, use_errno=True).prctl
    except (OSError, AttributeError):
        return
    prctl.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong]
    prctl.restype = ctypes.c_int
    name = threading.current_thread().name.encode()[:15]
    prctl(15, name, 0, 0, 0)                         # 15: PR_SET_NAME


SETTLE_POOL = ThreadPoolExecutor(max_workers=8, thread_name_prefix="swx-settle",
                                 initializer=_name_os_thread)

# query-path inference (REST forecasts, ad-hoc scoring) runs on its own
# small pool: a first-call model compile blocks its worker for as long
# as the compile takes and must never starve the scoring plane's settle
# pipeline above
QUERY_POOL = ThreadPoolExecutor(max_workers=2, thread_name_prefix="swx-query",
                                initializer=_name_os_thread)


def to_host(out) -> tuple:
    """Settle-thread read-back of one dispatch's result: `(host copy,
    instant the read began, instant this thread held the bytes)`. The
    thread touches no counter; the loop adds what it returns."""
    t_begin = time.monotonic()
    with TraceAnnotation("rule-processing.score.readback"):
        host = result_to_host(out)
    return host, t_begin, time.monotonic()


class DeviceStage:
    """One engine's device-stage accounting: `scoring.stage_device_s` and
    the three histograms that add up to it, the read-back's busy seconds,
    and the sampled spans (the parent, its children, the assembly)."""

    def __init__(self, metrics: MetricsRegistry, tracer: Tracer):
        self.tracer = tracer
        self.total = metrics.histogram("scoring.stage_device_s")
        self.enqueue = metrics.histogram("scoring.device_enqueue_s",
                                         buckets=QUARTER_OCTAVES)
        self.wait = metrics.histogram("scoring.device_wait_s",
                                      buckets=QUARTER_OCTAVES)
        self.wake = metrics.histogram("scoring.settle_wake_s",
                                      buckets=QUARTER_OCTAVES)

    def observe(self, reads: list[tuple], t0: float, t_enq: float,
                now: float) -> tuple[list, tuple]:
        """Account one chunk whose `to_host` results are `reads`, on the
        loop thread at `now`. Returns the host copies and the chunk's
        instants `(t0, t_enq, t_read, t_held, now)` for `record`: the
        read that finished last began at `t_read` and held the bytes at
        `t_held`."""
        _, t_read, t_held = max(reads, key=lambda r: r[2])
        self.total.observe(now - t0)
        self.enqueue.observe(t_enq - t0)
        self.wait.observe(t_held - t_enq)
        self.wake.observe(now - t_held)
        self.tracer.add_busy("rule-processing.score.readback",
                             sum(r[2] - r[1] for r in reads))
        return [r[0] for r in reads], (t0, t_enq, t_read, t_held, now)

    def record(self, traces: Iterable[tuple], tenant_id: str, instants: tuple,
               t_assembled: float) -> None:
        """The spans of one chunk for each sampled trace in it (a flush
        coalesces several admits; each keeps its own journey)."""
        tracer = self.tracer
        t0, t_enq, t_read, t_held, now = instants
        for trace_id, n_ev, *_ in traces:
            if not tracer.sampled(trace_id):
                continue
            tracer.record(trace_id, "rule-processing.score", tenant_id,
                          t0, now - t0, n_ev)
            tracer.record(trace_id, "rule-processing.score.enqueue",
                          tenant_id, t0, t_enq - t0, n_ev)
            tracer.record(trace_id, "rule-processing.score.device",
                          tenant_id, t_enq, t_held - t_enq, n_ev)
            tracer.record(trace_id, "rule-processing.score.readback",
                          tenant_id, t_read, t_held - t_read, n_ev)
            tracer.record(trace_id, "rule-processing.score.wake",
                          tenant_id, t_held, now - t_held, n_ev)
            tracer.record(trace_id, "rule-processing.assemble", tenant_id,
                          now, t_assembled - now, n_ev)


def booked(name: str) -> property:
    """An engine's read-only view of its flight book's `name`."""
    return property(lambda engine: getattr(engine.flights, name))


def bucket_for(n: int, buckets: tuple) -> int:
    """The smallest bucket that holds `n` rows, else the largest."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def merged_take(taken: list, tenant_id: str) -> tuple:
    """Admitted batches `(ids, values, ts, ingest, ctx, admitted at)` as
    one take: its columns, its delivery context, and a trace an admitted
    batch `(trace, events, admitted at)`. One batch passes with NO copy
    (the saturation steady state: a concatenate would memcpy every
    column for nothing); several merge their sources into one context."""
    traces = [(p[4].trace_id, p[0].shape[0], p[5]) for p in taken]
    if len(taken) == 1:
        dev, val, ts, ingest, ctx, _ = taken[0]
        return (dev, val.astype(np.float32, copy=False), ts, ingest, ctx,
                traces)
    dev, val, ts, ingest = (np.concatenate([p[i] for p in taken])
                            for i in range(4))
    sources = {p[4].source for p in taken}
    ctx = taken[0][4] if len(sources) == 1 else BatchContext(
        tenant_id=tenant_id, source="+".join(sorted(sources)),
        ingest_monotonic=min(p[4].ingest_monotonic for p in taken))
    return dev, val.astype(np.float32, copy=False), ts, ingest, ctx, traces


def occurrence_rounds(dev: np.ndarray, val: np.ndarray) -> tuple[list, bool]:
    """A take as the rounds the ring steps, `(ids, values, positions in
    the take)` each, and whether it arrived ascending. The ring wants a
    round's ids strictly ascending (scoring/stream.py, "Contract with the
    engines"): a take that arrives so (a gateway's frame) is one round as
    it stands, positions None; any other is sorted stably, and a device's
    k-th event goes in round k, so that a backlog coalesced into one
    flush scores as the same events flushed one tick at a time."""
    n = dev.shape[0]
    if n < 2 or bool((dev[1:] > dev[:-1]).all()):
        return [(dev, val, None)], True
    order = np.argsort(dev, kind="stable")
    sd, sv = dev[order], val[order]
    _, start, counts = np.unique(sd, return_index=True, return_counts=True)
    occurrence = np.arange(n) - np.repeat(start, counts)
    rounds = []
    for r in range(int(occurrence.max()) + 1):
        sel = occurrence == r
        rounds.append((sd[sel], sv[sel], order[sel]))
    return rounds, False


def place_scores(n: int, rounds: Iterable[tuple]) -> np.ndarray:
    """A take's `n` scores from its settled full read-backs `(scores, k,
    positions)`: a round's first `k` scores go to its positions."""
    scores = np.empty(n, np.float32)
    for row, k, positions in rounds:
        if positions is None:
            scores[:k] = row[:k]
        else:
            scores[positions] = row[:k]
    return scores


def anomalous_subset(rounds: Iterable[tuple],
                     overflow) -> tuple[np.ndarray, np.ndarray]:
    """A take's anomalies, `(positions, scores)`, from its settled sparse
    read-backs `((count, slots' positions, slots' scores), k, positions)`;
    those the step had no slot for are counted on `overflow`."""
    found: list[np.ndarray] = []
    scores: list[np.ndarray] = []
    for (count, pos, vals), k, positions in rounds:
        p, v, lost = sparse_take(count, pos, vals, k)
        if lost:
            overflow.inc(lost)
        if p.shape[0]:
            found.append(p if positions is None else positions[p])
            scores.append(v)
    if not found:
        return np.empty(0, np.int64), np.empty(0, np.float32)
    return np.concatenate(found), np.concatenate(scores)


class Flights:
    """An engine's book of dispatches from launch to delivery, and the
    metrics both engines keep of them: `inflight` counts a dispatch until
    its scores are published, `dispatch_count - settled_count ==
    inflight`, and the consumer's commit barrier reads `settled_through`."""

    def __init__(self, metrics: MetricsRegistry, tracer: Tracer):
        self.tracer = tracer
        self.inflight = 0
        self.dispatch_count = 0
        self.settled_count = 0
        self._outstanding: set[int] = set()    # launched, not yet settled
        # strong refs to the settle tasks: the loop keeps only weak ones,
        # and a settle collected mid-flight would leave `inflight` and
        # `_outstanding` stuck, so that the engine never flushes again
        self.tasks: set[asyncio.Task] = set()
        self.scored_meter = metrics.meter("scoring.events_scored")
        self.latency = metrics.histogram("scoring.e2e_latency_s")
        self.anomalies = metrics.counter("scoring.anomalies_detected")
        self.anomaly_overflow = metrics.counter("scoring.anomaly_overflow")
        self.dropped = metrics.counter("scoring.admissions_dropped")
        self.sink_failures = metrics.counter("scoring.sink_failures")
        # flush-path jit calls: chunks and rounds each count one
        self.dispatches = metrics.counter("scoring.dispatches")
        # takes whose ids arrived ascending: dispatched with no host sort
        self.ascending = metrics.counter("scoring.ring.ascending")
        # end-to-end latency in four stages:
        #   admit  = receiver arrival → admission (decode + bus hops + queue)
        #   batch  = admission → dispatch (deadline batching + inflight gate)
        #   device = dispatch → scores on host, in three parts (DeviceStage)
        #   sink   = settled → published (delivery/alert fan-out)
        self.stage_admit = metrics.histogram("scoring.stage_admit_s")
        self.stage_batch = metrics.histogram("scoring.stage_batch_s")
        self.device_stage = DeviceStage(metrics, tracer)
        self.stage_sink = metrics.histogram("scoring.stage_sink_s")

    @property
    def settled_through(self) -> int:
        """Every dispatch with seq < this has settled (delivery attempted)
        or been counted as dropped: settles finish out of order."""
        return min(self._outstanding) if self._outstanding \
            else self.dispatch_count

    def record_dispatch(self, traces: list, tenant_id: str,
                        t0: float) -> None:
        """Each trace's `rule-processing.dispatch` span, admission → `t0`:
        pure queue wait (batching window + inflight gate)."""
        for trace_id, n_ev, t_admit in traces:
            self.tracer.record(trace_id, "rule-processing.dispatch",
                               tenant_id, t_admit, max(t0 - t_admit, 0.0),
                               n_ev)

    def launch(self, executor: Executor, results: list, n_events: int,
               t0: float, t_enq: float, assemble: Callable,
               fut: Optional[asyncio.Future] = None,
               release: Optional[Callable] = None) -> None:
        """Book a dispatch and start its settle: its rounds' `results`
        are read back on `executor`, then `assemble(host copies, now)`
        returns its deliveries, `(tenant, traces, sink or None,
        ScoredBatch)` each; `fut` hears the first one's batch or the
        failure; `release` runs once the dispatch has left the book."""
        seq = self.dispatch_count
        self.dispatch_count += 1
        self.inflight += 1
        self._outstanding.add(seq)
        task = asyncio.get_running_loop().create_task(self._settle(
            executor, results, n_events, t0, t_enq, seq, assemble, fut,
            release), name="scoring-settle")
        self.tasks.add(task)
        task.add_done_callback(self.task_done)

    def task_done(self, task: asyncio.Task) -> None:
        self.tasks.discard(task)
        if not task.cancelled() and task.exception() is not None:
            # the settle's `finally` keeps the book right even here, but
            # an escape is a bug: surface it, not an unretrieved exception
            logger.error("settle task died unexpectedly",
                         exc_info=task.exception())

    async def _settle(self, executor, results, n_events, t0, t_enq, seq,
                      assemble, fut, release) -> None:
        # `inflight` covers the settle AND the delivery: the consumer's
        # commit barrier must not count a dispatch done before its scored
        # output has been published
        loop = asyncio.get_running_loop()
        try:
            try:
                reads = await asyncio.gather(*[
                    loop.run_in_executor(executor, to_host, out)
                    for out in results])
            except BaseException as exc:
                if fut is not None and not fut.done():
                    fut.set_exception(exc if isinstance(exc, Exception)
                                      else RuntimeError("settle cancelled"))
                # these events' scores are lost: the commit barrier that
                # advances past them is an explicit drop, not a silent one
                self.dropped.inc(n_events)
                if isinstance(exc, Exception):
                    logger.exception("scoring settle failed")
                    return
                raise
            # from here to the sinks the loop itself works (scores put
            # back, thresholds, ScoredBatches): a span, which starts where
            # the device stage's last part ends
            with self.tracer.span("rule-processing.assemble") as span:
                settled, instants = self.device_stage.observe(
                    reads, t0, t_enq, span.t_start)
                deliveries = assemble(settled, span.t_start)
            for tenant_id, traces, _, _ in deliveries:
                self.device_stage.record(traces, tenant_id, instants,
                                         span.t_end)
            if fut is not None and not fut.done():
                fut.set_result(deliveries[0][3])
            # the one place scores are published: in the order the
            # settles finish (ROADMAP D0 (b)), each take's tenant
            # concurrently with the others, a failure counted and kept
            # to its own tenant (kernel/egresslane.py deliver_scored)
            sends = [deliver_scored(sink, scored, self.sink_failures,
                                    self.stage_sink, label=f"tenant {tid}")
                     for tid, _, sink, scored in deliveries
                     if sink is not None]
            if len(sends) == 1:
                await sends[0]
            elif sends:
                await asyncio.gather(*sends)
        finally:
            self.inflight -= 1
            self.settled_count += 1
            self._outstanding.discard(seq)
            if release is not None:
                release()

    def scored(self, ctx, dev: np.ndarray, ts: np.ndarray,
               ingest: np.ndarray, now: float, rounds: list,
               threshold: float, version: int) -> ScoredBatch:
        """One take's `ScoredBatch` from its settled rounds `(read-back, k,
        positions)`, counted alike for both read-backs: every event was
        scored on the device, the sparse one ships fewer home."""
        n = dev.shape[0]
        self.scored_meter.mark(n)
        self.latency.observe_array(now - ingest)
        if isinstance(rounds[0][0], tuple):
            found, scores = anomalous_subset(rounds, self.anomaly_overflow)
            self.anomalies.inc(int(found.shape[0]))
            return ScoredBatch(ctx, dev[found], scores,
                               np.ones(found.shape[0], bool), ts[found],
                               model_version=version, total_scored=n)
        scores = place_scores(n, rounds)
        is_anom = scores >= threshold
        n_anom = int(is_anom.sum())
        if n_anom:
            self.anomalies.inc(n_anom)
        return ScoredBatch(ctx, dev, scores, is_anom, ts,
                           model_version=version)
