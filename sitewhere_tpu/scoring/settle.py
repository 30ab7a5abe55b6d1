"""Shared settle executor: a device→host readback blocks its caller
until the device has finished (how long on the chip's own host: not
measured) but releases the GIL and parallelizes across threads — so
every session and pool settles results on this one pool of workers
instead of blocking the event loop.

And the one place that says what the device stage is made of, for both
engines (`ScoringSession`, `SharedScoringPool`). A dispatched chunk's
`scoring.stage_device_s` runs from `t0` (before its `_dispatch`) to the
instant its task resumes on the loop; `DeviceStage` cuts that interval
at two instants into three parts that tile it:

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

import ctypes
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable

from jax.profiler import TraceAnnotation

from sitewhere_tpu.kernel.metrics import QUARTER_OCTAVES, MetricsRegistry
from sitewhere_tpu.kernel.tracing import Tracer
from sitewhere_tpu.scoring.stream import result_to_host


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
