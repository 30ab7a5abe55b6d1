"""Per-event pipeline tracing [SURVEY.md §5.1] — the trace spine of the
pipeline flight recorder.

The reference has no distributed tracing in core (logging only); the
rebuild carries a trace context in every batch envelope
(`BatchContext.trace_id`, stamped at the receiver) and records one SPAN
per pipeline stage into bounded per-stage rings:

    receiver → decode → enrich → persist → dispatch → score → egress.publish

plus the off-ramp stages (deferred spool/replay, DLQ quarantine/replay).
The stage inventory lives in `analysis/registry.py` (`TRACE_STAGES`) —
swxlint TRC01 resolves every recorded stage literal against it, exactly
as MET01 does for metric names — and each stage is classified as
*queue* (time spent waiting: receiver arrival → decode, admission →
dispatch) or *service* (time spent working), so the critical-path
report can answer "where does paced p99 live" with a queue-wait vs
service-time split.

Wire-hop spans (kernel/wire.py) keep their meaning across transport
modes: `wire.produce` is the append RPC's service time, `wire.poll` the
broker-append→delivery queue wait — under streaming prefetch the
delivery instant is the deliver frame's ARRIVAL (credit delivery), so
the hop's queue wait never absorbs time records spend in the consumer's
own prefetch buffer (that residency shows up downstream, where it
belongs).

Sampling keeps the hot path honest: at 1M events/s nobody can afford a
span per batch per stage, so only every `sample`-th trace id records
(trace ids are dense counters, so modulo sampling is uniform). Spans
ring per STAGE (one chatty stage — a busy egress shard, a flapping DLQ
— can no longer evict every other stage's spans from a shared ring).

`Tracer.spans()` / `Tracer.trace(trace_id)` are the query surface (REST
exposes them, with tenant filtering and pagination); `record()` is the
single write path into the rings (kept lean: the hot pipeline calls it
per batch per stage).

`Tracer.span(stage, ...)` is the primitive for a stage that is
synchronous code (no `await` inside): one `with` that (a) adds the
elapsed seconds to the counter `busy.<stage>`, every time and not only
for sampled traces, so that a layer's busy share is a window delta over
seconds; (b) records the sampled span as `record()` does; (c) is a
`jax.profiler.TraceAnnotation` named `stage`, so that while a
`jax.profiler` trace runs (`start_trace` with `host_tracer_level >= 1`)
the span lands in the trace's `/host:CPU` plane on the line of the
thread that ran it, on the same clock as the device's operations
(benchmarks/hostspans.py lays idle gaps of the device to them). With no
trace running an annotation costs well under a microsecond. A stage
whose interval holds an `await` keeps `record()`: its wall time is not
busy time, and an annotation left open across a suspension would cover
whatever else the loop ran meanwhile.

`Tracer.watch_loop(loop)` is the account of the code that DOES await:
the serving loop's own second, split with nothing left over,

    window = loop.select_s + busy.loop
    busy.loop = sum of busy.loop.<operator> + busy.loop.callbacks

A task's step (one `send` or `throw` of its coroutine, synchronous by
construction) is timed by a wrapper that the loop's task factory puts
round the coroutine, and its seconds go to the task's operator: the
rightmost `/`-element of the task's name that, less a trailing
`-<digits>`, is in `analysis/registry.py`'s `LOOP_OPERATORS`, else
`other`. The selector's wait is the idle time (`loop.select_s`), timed
by a proxy round the loop's selector; what a stretch between two waits
holds beside its steps is `busy.loop.callbacks` (socket reads, timers,
done-callbacks, tasks older than the watch), and what of the stretch no
outermost `span()` and no collection covered on the loop's thread is
`busy.loop.unspanned`: the dark share, as a counter. While a
`jax.profiler` trace runs each step is also an annotation
`loop.<operator>` and each wait one named `loop.select`, so the loop
thread's line is tiled by operators, with the stage spans nested inside
them, and by waits; the gaps that are left are the callbacks.
A step or a stretch of callbacks of a millisecond or more goes into the
histogram `loop.long_step_s`; one of `stall_s` or more is kept, with its
operator, task and covering stage, in a bounded ring (`slow_steps()`).
"""

from __future__ import annotations

import asyncio
import collections.abc
import gc
import itertools
import re
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass
from typing import Iterable, Optional

from sitewhere_tpu.kernel.metrics import (
    QUARTER_OCTAVES,
    Counter,
    Histogram,
    MetricsRegistry,
)


@dataclass(frozen=True, slots=True)
class Span:
    trace_id: int
    stage: str            # e.g. "event-sources.decode"
    tenant_id: str
    t_start: float        # monotonic
    duration_s: float
    n_events: int

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "stage": self.stage,
                "tenant": self.tenant_id, "t_start": self.t_start,
                "duration_ms": round(self.duration_s * 1e3, 3),
                "n_events": self.n_events}


class _Span:
    """One `Tracer.span()` in flight. `t_start` and, after the block,
    `t_end` are its `time.monotonic()` instants; `n_events` may be set
    inside the block (a decode learns its count by decoding)."""

    __slots__ = ("_tracer", "_annotation", "_watch", "stage", "trace_id",
                 "tenant_id", "n_events", "t_start", "t_end")

    def __init__(self, tracer: "Tracer", stage: str, trace_id: int,
                 tenant_id: str, n_events: int):
        self._tracer = tracer
        self._annotation = tracer._annotate(stage)
        self._watch = None
        self.stage = stage
        self.trace_id = trace_id
        self.tenant_id = tenant_id
        self.n_events = n_events
        self.t_start = self.t_end = 0.0

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self.t_start = time.monotonic()
        watch = self._tracer._watch
        if watch is not None and watch.thread == threading.get_ident():
            self._watch = watch
            watch.depth += 1
        return self

    def __exit__(self, *exc) -> None:
        self.t_end = time.monotonic()
        self._annotation.__exit__(*exc)
        watch = self._watch
        if watch is not None:
            watch.depth -= 1
            if watch.depth == 0:        # outermost on the loop's thread
                watch.cover(self.stage, self.t_start,
                            self.t_end - self.t_start)
        self._tracer._close(self)


# -- the loop's account (Tracer.watch_loop) -----------------------------------

_now = time.monotonic
LONG_STEP_S = 1e-3      # from here a step pays a histogram observation
OTHER = "other"         # the operator of a task no inventory name fits
SELECT_SPAN = "loop.select"     # the selector's wait, on a profiler trace
_SHARD = re.compile(r"-\d+$")


def operator_of(task_name: str) -> str:
    """The operator a task's seconds go to: the rightmost `/`-element of
    its name that, with a trailing `-<digits>` dropped, is in
    `LOOP_OPERATORS`, else `other`. Only inventory names come out, so
    what a tenant, an instance or a model is called never names a
    counter."""
    from sitewhere_tpu.analysis.registry import LOOP_OPERATORS

    for element in reversed(task_name.split("/")):
        element = _SHARD.sub("", element)
        if element in LOOP_OPERATORS:
            return element
    return OTHER


class _Operator:
    """One operator's counter, steps and annotation name."""

    __slots__ = ("name", "busy", "steps", "span_name")

    def __init__(self, name: str, busy: Counter):
        self.name = name
        self.busy = busy
        self.steps = 0
        self.span_name = f"loop.{name}"


class _LoopWatch:
    """What one `watch_loop` keeps. A stretch runs from one return of the
    selector's `select` (`t_stretch`) to its next call; `mark` is where
    the last step in it ended (or the stretch began), so that `now -
    mark` is a run of callbacks that are no task; `steps_s` and
    `covered_s` are the stretch's seconds inside steps, and inside
    outermost spans and collections."""

    __slots__ = ("tracer", "loop", "thread", "live", "factory",
                 "selector", "operators", "busy", "select_s", "callbacks",
                 "unspanned", "long_steps", "slow", "t_stretch", "mark",
                 "steps_s", "covered_s", "depth", "waiting", "gc_waiting_s",
                 "long_spans", "tracing", "annotation", "by_task_name")

    def __init__(self, tracer: "Tracer", loop):
        metrics = tracer.metrics
        self.tracer = tracer
        self.loop = loop
        # a step is an annotation `loop.<operator>` only while a profiler
        # trace runs: it is too frequent to pay a third of a microsecond
        # for one that nothing records
        self.annotation = tracer._annotation_class()
        self.tracing = getattr(self.annotation, "is_enabled", lambda: True)
        self.thread = threading.get_ident()
        self.live = True
        # ours on the loop; `selector` stays None on a loop without the seam
        self.factory = self.selector = None
        self.operators: dict[str, _Operator] = {}
        self.by_task_name: dict[str, _Operator] = {}
        self.busy = tracer._busy_counter("loop")
        self.select_s = metrics.counter("loop.select_s")
        self.callbacks = tracer._busy_counter("loop.callbacks")
        self.unspanned = tracer._busy_counter("loop.unspanned")
        self.long_steps = metrics.histogram(
            "loop.long_step_s",
            buckets=[b for b in QUARTER_OCTAVES if b > LONG_STEP_S])
        self.slow = metrics.counter("loop.slow_steps")
        self.t_stretch = self.mark = _now()
        self.steps_s = self.covered_s = 0.0
        self.depth = 0
        self.waiting = False
        self.gc_waiting_s = 0.0
        # outermost spans, and collections, of a millisecond or more on
        # the loop's thread, newest last: what a slow step names its
        # stage and its collector's seconds by
        self.long_spans: deque[tuple] = deque(maxlen=16)

    def operator(self, task) -> _Operator:
        """The operator of `task` by its name now (the rule is
        `operator_of`'s)."""
        task_name = task.get_name() if task is not None else ""
        operator = self.by_task_name.get(task_name)
        if operator is None:
            name = operator_of(task_name)
            operator = self.operators.get(name)
            if operator is None:
                operator = self.operators[name] = _Operator(
                    name, self.tracer._busy_counter(f"loop.{name}"))
            if len(self.by_task_name) < 4096:   # names are the fleet's
                self.by_task_name[task_name] = operator
        return operator

    def cover(self, stage: str, t_start: float, seconds: float,
              inside_a_span: bool = False) -> None:
        """An outermost span or a collection closed on the loop's
        thread: its seconds are not dark (a collection inside a span is
        the span's already), and a slow step can name it."""
        if not inside_a_span:
            self.covered_s += seconds
        if seconds >= LONG_STEP_S:
            self.long_spans.append((t_start, seconds, stage))

    def end_stretch(self, now: float) -> None:
        """The stretch since the selector last returned is over (it is
        about to wait again, or the watch ends): its seconds go to
        `busy.loop`, and what of them was no step, and what nothing
        covered, to their counters."""
        if now - self.mark >= LONG_STEP_S:
            self.long_step(None, self.mark, now - self.mark)
        stretch = now - self.t_stretch
        self.busy.value += stretch
        self.callbacks.value += stretch - self.steps_s
        self.unspanned.value += stretch - self.covered_s
        self.steps_s = self.covered_s = 0.0
        self.t_stretch = self.mark = now

    def long_step(self, operator: Optional[_Operator], t_start: float,
                  seconds: float) -> None:
        """A step (or, with no operator, a run of callbacks) of a
        millisecond or more; from `stall_s` it is kept by name."""
        self.long_steps.observe(seconds)
        tracer = self.tracer
        if seconds < tracer.stall_s:
            return
        self.slow.inc()
        task = asyncio.current_task(self.loop) if operator else None
        inside = [s for s in self.long_spans if s[0] >= t_start]
        stage = max((s for s in inside if not s[2].startswith("gc.")),
                    key=lambda s: s[1], default=None)
        tracer._slow_steps.append({
            "operator": operator.name if operator else "callbacks",
            "task": task.get_name() if task is not None else None,
            "t_start": t_start,
            "seconds": seconds,
            "stage": stage[2] if stage else None,
            "stage_s": stage[1] if stage else 0.0,
            "gc_s": sum(s[1] for s in inside if s[2].startswith("gc.")),
        })


class _TaskSteps(collections.abc.Coroutine):
    """A task's coroutine with each step (one `send` or `throw`) timed.
    Everything else reads through to the coroutine: `cr_frame`,
    `cr_code`, `cr_running`, `cr_await`, `__name__`, `__qualname__`, so
    `Task.get_stack()`, `repr(task)` and a crash log read as before."""

    __slots__ = ("_coro", "_watch", "_operator", "_thrown")

    def __init__(self, coro, watch: _LoopWatch):
        self._coro = coro
        self._watch = watch
        self._operator: Optional[_Operator] = None
        self._thrown: Optional[tuple] = None

    def __getattr__(self, name: str):
        return getattr(self._coro, name)

    def __await__(self):
        return self._coro.__await__()

    def close(self):
        return self._coro.close()

    def throw(self, *exc):
        # a step like any other: parked here, thrown in `send`
        self._thrown = exc
        return self.send(None)

    def send(self, value):
        watch = self._watch
        thrown = self._thrown
        if not watch.live:
            if thrown is None:
                return self._coro.send(value)
            self._thrown = None
            return self._coro.throw(*thrown)
        operator = self._operator
        first = operator is None
        if first:
            # `create_task` names the task after the factory returned, and
            # a connection handler names itself at its first line: the
            # name is read again when this first step's seconds are folded
            operator = watch.operator(asyncio.current_task(watch.loop))
        annotation = None
        if watch.tracing():         # a profiler trace is running
            annotation = watch.annotation(operator.span_name)
            annotation.__enter__()
        t0 = _now()
        if t0 - watch.mark >= LONG_STEP_S and watch.selector is not None:
            watch.long_step(None, watch.mark, t0 - watch.mark)
        try:
            if thrown is None:
                return self._coro.send(value)
            self._thrown = None
            return self._coro.throw(*thrown)
        finally:
            t1 = _now()
            if annotation is not None:
                annotation.__exit__(None, None, None)
            if first:
                operator = self._operator = watch.operator(
                    asyncio.current_task(watch.loop))
            if watch.live:      # not the step that ended the watch
                seconds = t1 - t0
                operator.busy.value += seconds
                operator.steps += 1
                watch.steps_s += seconds
                watch.mark = t1
                if seconds >= LONG_STEP_S:
                    watch.long_step(operator, t0, seconds)


class _TaskFactory:
    """The loop's task factory while it is watched: the task that the
    factory before it (or `asyncio.Task`) makes, of the wrapped
    coroutine."""

    def __init__(self, watch: _LoopWatch, before):
        self.watch = watch
        self.before = before

    def __call__(self, loop, coro, **kwargs):
        if self.watch.live:
            coro = _TaskSteps(coro, self.watch)
        if self.before is not None:
            return self.before(loop, coro, **kwargs)
        return asyncio.Task(coro, loop=loop, **kwargs)


class _TimedSelector:
    """The loop's selector with its wait timed: the loop is idle exactly
    while it sits in `select`. Everything else is the selector's own."""

    def __init__(self, watch: _LoopWatch, before):
        self.watch = watch
        self.before = before

    def __getattr__(self, name: str):
        return getattr(self.before, name)

    def select(self, timeout=None):
        watch = self.watch
        if not watch.live:
            return self.before.select(timeout)
        t0 = _now()
        watch.end_stretch(t0)
        annotation = None
        if watch.tracing():         # on a trace the wait has a name too,
            annotation = watch.annotation(SELECT_SPAN)  # callbacks are
            annotation.__enter__()                      # the gaps
        watch.waiting = True
        try:
            return self.before.select(timeout)
        finally:
            watch.waiting = False
            if annotation is not None:
                annotation.__exit__(None, None, None)
            t1 = watch.t_stretch = watch.mark = _now()
            watch.select_s.value += t1 - t0
            if watch.gc_waiting_s:
                # a collection that ran on this thread between the two
                # instants (the selector's own lists) was no wait
                collected, watch.gc_waiting_s = watch.gc_waiting_s, 0.0
                watch.select_s.value -= collected
                watch.busy.value += collected
                watch.callbacks.value += collected


def _selector_of(loop):
    """The seam where a loop waits: asyncio's selector loops keep their
    selector as `_selector` and call its `select` once an iteration
    (tests/test_loop_watch.py pins that to the interpreter in use). No
    public seam exists on a loop that is already running. None where the
    loop has none: it is then watched for its steps alone."""
    selector = getattr(loop, "_selector", None)
    return selector if callable(getattr(selector, "select", None)) else None


def _live_before(link):
    """What `link` (a factory or a selector of ours) was put round, with
    dead links of its own kind skipped: two runtimes on one loop may
    stop in the order they started."""
    before = link.before
    while isinstance(before, type(link)) and not before.watch.live:
        before = before.before
    return before


class Tracer:
    """Bounded per-stage span rings with modulo sampling, and the
    `busy.<stage>` counters of `span()`. One per runtime. `capacity` is
    the total span budget; each stage's ring gets `stage_capacity`
    (default `capacity // 8`, min 64) so stages evict only their own
    history. `metrics` is the registry the busy counters live in (the
    runtime's; a tracer built without one keeps its own). `watch_loop`
    adds the serving loop's account (the module's docstring)."""

    def __init__(self, capacity: int = 4096, sample: int = 64,
                 stage_capacity: int = 0,
                 metrics: Optional[MetricsRegistry] = None,
                 stall_s: float = 0.1):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._busy: dict[str, Counter] = {}
        self._annotation_cls = None
        self._gc_open: Optional[tuple] = None
        # the loop's account (watch_loop): a step of `stall_s` or more is
        # kept by name (the runtime passes its `observe_stall_ms`)
        self.stall_s = stall_s
        self._watch: Optional[_LoopWatch] = None
        self._slow_steps: deque[dict] = deque(maxlen=32)
        self.sample = max(int(sample), 1)
        self.stage_capacity = (max(int(stage_capacity), 1)
                               if stage_capacity
                               else max(capacity // 8, 64))
        self._rings: dict[str, deque[Span]] = {}
        self._ids = itertools.count(1)
        # fleet-wide id scope (set_origin): high bits of every id this
        # process MINTS. 0 = unscoped (single-process deployments keep
        # their small dense ids)
        self._origin = 0

    def set_origin(self, key: str) -> None:
        """Scope trace ids minted HERE to this process: the high 31
        bits become a hash of `key` (worker id), the low 32 bits stay
        the dense counter. Two fleet processes can then never mint the
        same id, so a fleet-merged trace view (`FleetObserver`,
        `ApiServer` trace op) attributes every span unambiguously —
        while `sampled()` stays a pure function of the id, so EVERY
        process along a batch's journey makes the same record/skip
        decision for a trace some other process stamped. Masked to 31
        bits: the full id must stay inside the wire codec's i64."""
        self._origin = (zlib.crc32(key.encode()) & 0x7FFFFFFF) << 32

    @property
    def origin(self) -> int:
        return self._origin

    def new_trace_id(self) -> int:
        """Dense trace ids (stamped at the receiver), origin-scoped
        when `set_origin` ran (fleet workers)."""
        return self._origin | next(self._ids)

    def sampled(self, trace_id: int) -> bool:
        return trace_id > 0 and trace_id % self.sample == 0

    def record(self, trace_id: int, stage: str, tenant_id: str,
               t_start: float, duration_s: float, n_events: int = 0) -> None:
        if not self.sampled(trace_id):
            return
        ring = self._rings.get(stage)
        if ring is None:
            ring = self._rings[stage] = deque(maxlen=self.stage_capacity)
        ring.append(Span(trace_id, stage, tenant_id, t_start,
                         duration_s, n_events))

    # -- busy time and the profiler's clock ----------------------------------

    def _annotation_class(self):
        """`jax.profiler.TraceAnnotation`, imported when first asked for:
        nothing under kernel/ imports JAX while it is imported."""
        cls = self._annotation_cls
        if cls is None:
            from jax.profiler import TraceAnnotation as cls

            self._annotation_cls = cls
        return cls

    def _annotate(self, name: str):
        return self._annotation_class()(name)

    def span(self, stage: str, trace_id: int = 0, tenant_id: str = "",
             n_events: int = 0) -> _Span:
        """`with tracer.span(stage, ...):` around synchronous code: busy
        seconds, the sampled span, and an annotation on a running
        `jax.profiler` trace (the module's docstring)."""
        return _Span(self, stage, trace_id, tenant_id, n_events)

    def _close(self, span: _Span) -> None:
        seconds = span.t_end - span.t_start
        self.add_busy(span.stage, seconds)
        self.record(span.trace_id, span.stage, span.tenant_id,
                    span.t_start, seconds, span.n_events)

    def _busy_counter(self, stage: str) -> Counter:
        counter = self._busy.get(stage)
        if counter is None:
            counter = self._busy[stage] = self.metrics.counter(
                f"busy.{stage}")
        return counter

    def add_busy(self, stage: str, seconds: float) -> None:
        """Add to `busy.<stage>`. A plain float add, so from the thread
        that owns the counter only: a worker thread hands its instants
        back and the event loop adds them."""
        self._busy_counter(stage).inc(seconds)

    def watch_gc(self) -> None:
        """Put the collector's pauses on both clocks: each collection is
        an annotation `gc.gen<N>` on the thread it ran on, and its
        seconds go to `busy.gc`. (A collection runs with the interpreter
        lock held and never inside another, so the add is safe from
        whichever thread tripped it.)"""
        self._annotation_class()        # imported here, not in a collection
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def unwatch_gc(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            annotation = self._annotate(f"gc.gen{info['generation']}")
            annotation.__enter__()
            self._gc_open = (annotation, time.monotonic())
        elif self._gc_open is not None:
            annotation, t0 = self._gc_open
            self._gc_open = None
            seconds = time.monotonic() - t0
            self.add_busy("gc", seconds)
            annotation.__exit__(None, None, None)
            watch = self._watch
            if watch is not None and watch.thread == threading.get_ident():
                if watch.waiting:
                    watch.gc_waiting_s += seconds
                else:
                    watch.cover(f"gc.gen{info['generation']}", t0, seconds,
                                inside_a_span=watch.depth > 0)

    # -- the loop's account ---------------------------------------------------

    def watch_loop(self, loop) -> None:
        """Account for `loop`'s time from now on (the module's
        docstring), called on the loop's own thread. Tasks made from here
        on have their steps timed; where the loop keeps its selector as
        `_selector` (asyncio's selector loops do) its waits are timed
        too, and where it does not the steps are still counted and
        `busy.loop` is not reported. Watching the loop that is watched
        already changes nothing."""
        watch = self._watch
        if watch is not None:
            if watch.loop is loop:
                return
            self.unwatch_loop()
        watch = self._watch = _LoopWatch(self, loop)
        watch.factory = _TaskFactory(watch, loop.get_task_factory())
        loop.set_task_factory(watch.factory)
        selector = _selector_of(loop)
        if selector is not None:
            watch.selector = loop._selector = _TimedSelector(watch, selector)

    def unwatch_loop(self) -> None:
        """Leave the loop's factory and selector as `watch_loop` found
        them. Tasks made meanwhile keep their wrappers, which from now
        on pass straight through."""
        watch, self._watch = self._watch, None
        if watch is None:
            return
        watch.live = False
        loop = watch.loop
        if loop.get_task_factory() is watch.factory:
            loop.set_task_factory(_live_before(watch.factory))
        if watch.selector is not None:
            watch.end_stretch(_now())       # the stretch this call is in
            if loop._selector is watch.selector:
                loop._selector = _live_before(watch.selector)

    def slow_steps(self) -> list[dict]:
        """The steps, and runs of callbacks, of `stall_s` or more that
        the ring still holds, oldest first: operator, task, start on
        `time.monotonic()`, seconds, and the stage of the span that
        covered most of it."""
        return list(self._slow_steps)

    def loop_report(self) -> Optional[dict]:
        """The loop's account since `watch_loop`, for `observe_report()`:
        seconds busy and waiting, each operator's seconds and steps,
        and the slow-step ring. None while no loop is watched."""
        watch = self._watch
        if watch is None:
            return None
        def seconds(counter: Counter) -> Optional[float]:
            # the four that only a timed selector can give
            return round(counter.value, 6) if watch.selector is not None \
                else None

        operators = {
            name: {"busy_s": round(op.busy.value, 6), "steps": op.steps}
            for name, op in sorted(watch.operators.items(),
                                   key=lambda kv: -kv[1].busy.value)}
        return {
            "busy_s": seconds(watch.busy),
            "select_s": seconds(watch.select_s),
            "callbacks_s": seconds(watch.callbacks),
            "unspanned_s": seconds(watch.unspanned),
            "operators": operators,
            "slow_step_threshold_ms": round(self.stall_s * 1e3, 1),
            "slow_steps": [
                {**s, "seconds": round(s["seconds"], 6),
                 "stage_s": round(s["stage_s"], 6),
                 "gc_s": round(s["gc_s"], 6)} for s in self._slow_steps],
        }

    # -- query surface -----------------------------------------------------

    def _all(self) -> Iterable[Span]:
        for ring in self._rings.values():
            yield from ring

    def spans(self, stage: Optional[str] = None,
              tenant: Optional[str] = None,
              limit: int = 256, offset: int = 0) -> list[Span]:
        """Newest-first span listing, filterable by stage and tenant,
        paginated with (offset, limit) — the REST listing surface."""
        if stage is not None:
            source: Iterable[Span] = self._rings.get(stage, ())
        else:
            source = self._all()
        out = [s for s in source
               if tenant is None or s.tenant_id == tenant]
        out.sort(key=lambda s: s.t_start, reverse=True)
        if offset:
            out = out[offset:]
        return out[:limit] if limit >= 0 else out

    def trace(self, trace_id: int,
              tenant: Optional[str] = None) -> list[Span]:
        """Every recorded span of one trace, in time order — the
        pipeline's journey for one ingest batch, receiver →
        egress.publish (plus any off-ramp spans it took)."""
        return sorted((s for s in self._all()
                       if s.trace_id == trace_id
                       and (tenant is None or s.tenant_id == tenant)),
                      key=lambda s: s.t_start)

    def _stage_hist(self, spans: Iterable[Span]) -> tuple[Histogram, int,
                                                          int, float]:
        hist = Histogram("stage")
        events = 0
        count = 0
        total = 0.0
        for s in spans:
            hist.observe(s.duration_s)
            events += s.n_events
            count += 1
            total += s.duration_s
        return hist, count, events, total

    def stage_summary(self, tenant: Optional[str] = None) -> dict[str, dict]:
        """Per-stage p50/p95/p99 duration + event counts over the
        sampled spans (ops dashboard; quantiles via the same
        `Histogram.quantile` the metrics registry uses — the old
        mean/max pair hid exactly the tail this exists to show)."""
        out: dict[str, dict] = {}
        for stage in sorted(self._rings):
            spans = [s for s in self._rings[stage]
                     if tenant is None or s.tenant_id == tenant]
            if not spans:
                continue
            hist, count, events, total = self._stage_hist(spans)
            out[stage] = {
                "count": count,
                "p50_ms": round(hist.quantile(0.50) * 1e3, 3),
                "p95_ms": round(hist.quantile(0.95) * 1e3, 3),
                "p99_ms": round(hist.quantile(0.99) * 1e3, 3),
                "mean_ms": round(total / count * 1e3, 3),
                "max_ms": round(hist._max * 1e3, 3),
                "events": events,
            }
        return out

    def stage_export(self, tenant: Optional[str] = None) -> dict[str, dict]:
        """Per-stage summary in MERGEABLE form: histogram bucket counts
        beside count/events/total/max. Per-worker p99s cannot be
        averaged into a fleet p99 — bucket-wise histogram merge keeps
        fleet quantiles exact to bucket resolution, which is what the
        telemetry export publishes and `merge_stage_exports` folds
        (kernel/observe.py beat → fleet/observer.py)."""
        out: dict[str, dict] = {}
        for stage in sorted(self._rings):
            spans = [s for s in self._rings[stage]
                     if tenant is None or s.tenant_id == tenant]
            if not spans:
                continue
            hist, count, events, total = self._stage_hist(spans)
            out[stage] = {
                "count": count,
                "events": events,
                "total_s": total,
                "max_s": hist._max,
                "buckets": list(hist.buckets),
                "counts": list(hist.counts),
            }
        return out

    def critical_path(self, tenant: Optional[str] = None) -> dict:
        """The critical-path report over sampled traces: per-stage
        quantiles in pipeline order, each stage classified queue vs
        service (analysis/registry.py TRACE_STAGES), and the queue-wait
        vs service-time p99 split — "where does paced p99 live".

        Unregistered stages (tests, future drift) still report, with
        kind "unknown"; TRC01 is the gate that keeps the live tree's
        stages registered."""
        report = _critical_path(self.stage_summary(tenant=tenant))
        report["sample"] = self.sample
        return report


def _critical_path(rows: dict[str, dict]) -> dict:
    """Per-stage summary rows (`p99_ms`, `count`, ...) to the report:
    pipeline order, each stage's kind, and the queue-wait vs service
    p99 sums. A stage that is part of another (`TRACE_STAGE_PARENT`)
    carries its `parent` and stays out of the sums: its time is already
    in its parent's."""
    from sitewhere_tpu.analysis.registry import (
        TRACE_STAGE_PARENT,
        TRACE_STAGES,
    )

    kinds = dict(TRACE_STAGES)
    order = {name: i for i, (name, _) in enumerate(TRACE_STAGES)}
    stages: dict[str, dict] = {}
    split = {"queue": 0.0, "service": 0.0}
    span_count = 0
    for stage in sorted(rows, key=lambda s: order.get(s, 1000)):
        kind = kinds.get(stage, "unknown")
        row = stages[stage] = {**rows[stage], "kind": kind}
        span_count += row["count"]
        parent = TRACE_STAGE_PARENT.get(stage)
        if parent is not None:
            row["parent"] = parent
        elif kind in split:
            split[kind] += row["p99_ms"]
    return {
        "stages": stages,
        "span_count": span_count,
        "queue_wait_p99_ms": round(split["queue"], 3),
        "service_p99_ms": round(split["service"], 3),
    }


def merge_stage_exports(exports: Iterable[dict]) -> dict:
    """Fold per-process `stage_export` dicts into ONE fleet critical
    path: bucket counts merge additively per stage, quantiles are read
    off the merged histogram, and the queue-vs-service split is
    computed exactly as `Tracer.critical_path` does locally — the
    fleet-level answer to "where does paced p99 live" when the spine
    crosses worker processes (fleet/observer.py)."""
    merged: dict[str, dict] = {}
    for export in exports:
        for stage, row in (export or {}).items():
            agg = merged.get(stage)
            if agg is None:
                agg = merged[stage] = {
                    "count": 0, "events": 0, "total_s": 0.0, "max_s": 0.0,
                    "buckets": list(row.get("buckets") or ()),
                    "counts": [0] * len(row.get("counts") or ()),
                    "mixed": False,
                }
            agg["count"] += int(row.get("count", 0))
            agg["events"] += int(row.get("events", 0))
            agg["total_s"] += float(row.get("total_s", 0.0))
            agg["max_s"] = max(agg["max_s"], float(row.get("max_s", 0.0)))
            counts = row.get("counts") or ()
            if agg["mixed"]:
                continue
            if len(counts) == len(agg["counts"]):
                for i, c in enumerate(counts):
                    agg["counts"][i] += int(c)
            else:
                # bucket-shape drift across versions: bucket fidelity
                # is unrecoverable for this stage — flag it ONCE and
                # report quantiles as the max upper bound below, the
                # same answer whatever order exports arrive in
                agg["mixed"] = True
    rows: dict[str, dict] = {}
    for stage, agg in merged.items():
        if agg["mixed"]:
            # count-only merge: the honest quantile is unknowable, so
            # every quantile reports the conservative max upper bound
            q50 = q95 = q99 = agg["max_s"]
        else:
            hist = Histogram("stage", buckets=agg["buckets"] or None)
            hist.counts = list(agg["counts"]) + [0] * (
                len(hist.buckets) + 1 - len(agg["counts"]))
            hist.count = agg["count"]
            hist._max = agg["max_s"]
            q50, q95, q99 = (hist.quantile(0.50), hist.quantile(0.95),
                             hist.quantile(0.99))
        rows[stage] = {
            "count": agg["count"],
            "p50_ms": round(q50 * 1e3, 3),
            "p95_ms": round(q95 * 1e3, 3),
            "p99_ms": round(q99 * 1e3, 3),
            "mean_ms": round(agg["total_s"] / max(agg["count"], 1) * 1e3, 3),
            "max_ms": round(agg["max_s"] * 1e3, 3),
            "events": agg["events"],
        }
    return _critical_path(rows)
