"""The opened device stage (kernel/tracing.py `Tracer.span`,
scoring/settle.py `DeviceStage`): the three child durations of every
dispatch add up to its `scoring.stage_device_s` observation on both
engines, a split stage is not counted twice on the critical path,
`span` feeds `busy.<stage>` and the sampled rings and is held to
`TRACE_STAGES` by TRC01, the spans land on a `jax.profiler` trace on the
thread that ran them, the collector's pauses are counted, and the jitted
ring steps carry their named scopes under the module name the
benchmark's trace reduction keys on."""

import gc

import jax
import numpy as np
import pytest

from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.kernel.tracing import Tracer, merge_stage_exports
from sitewhere_tpu.models import build_model
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.pool import PoolConfig, SharedScoringPool
from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession

from tests.test_pipeline import wait_until

CHILDREN = ("scoring.device_enqueue_s", "scoring.device_wait_s",
            "scoring.settle_wake_s")
SCORE = "rule-processing.score"


def _batch(devices, value=21.0, t=10.0, trace_id=0, tid="t"):
    devices = np.asarray(devices, np.uint32)
    n = devices.shape[0]
    return MeasurementBatch(
        BatchContext(tenant_id=tid, source="test", trace_id=trace_id),
        devices, np.zeros(n, np.uint16), np.full(n, value, np.float32),
        np.full(n, t))


def _children_tile_the_stage(metrics, dispatches):
    total = metrics.histogram("scoring.stage_device_s")
    parts = [metrics.histogram(name) for name in CHILDREN]
    assert total.count == dispatches
    assert [h.count for h in parts] == [dispatches] * 3
    assert all(h.sum > 0 for h in parts)
    assert sum(h.sum for h in parts) == pytest.approx(total.sum, rel=1e-9)
    # quarter octaves from 10 us to 1 s, so that medians can be set
    # beside each other
    assert parts[0].buckets[0] == 1e-5 and parts[0].buckets[4] == 2e-5
    assert parts[0].buckets[-1] >= 0.9


def test_children_add_up_to_the_device_stage_in_the_session(run):
    async def main():
        metrics = MetricsRegistry()
        tracer = Tracer(sample=1, metrics=metrics)
        session = ScoringSession(
            build_model("lstm-stream", window=16, hidden=8),
            TelemetryStore(history=32, initial_devices=64), metrics,
            ScoringConfig(buckets=(32,), threshold=4.0), tracer=tracer)
        session.warmup()
        # one round; three occurrence rounds in one chunk; two chunks
        flushes = [np.arange(20), np.array([1, 2, 3, 1, 2, 1]),
                   np.arange(50)]
        for k, devices in enumerate(flushes):
            session.admit(_batch(devices, t=10.0 + k, trace_id=k + 1))
            scored = await session.flush()
            assert len(scored) == len(devices)
        _children_tile_the_stage(metrics, dispatches=4)
        # the spans tile their parent too (the one-chunk flushes' traces)
        for trace_id in (1, 2):
            spans = {s.stage: s for s in tracer.trace(trace_id)}
            parent = spans[SCORE]
            enq, dev, wake = (spans[f"{SCORE}.{part}"]
                              for part in ("enqueue", "device", "wake"))
            assert enq.t_start == parent.t_start
            assert dev.t_start == pytest.approx(enq.t_start + enq.duration_s)
            assert wake.t_start == pytest.approx(dev.t_start + dev.duration_s)
            assert enq.duration_s + dev.duration_s + wake.duration_s \
                == pytest.approx(parent.duration_s)
            # the settle thread's blocking read lies inside the device part
            read = spans[f"{SCORE}.readback"]
            assert dev.t_start <= read.t_start
            assert read.t_start + read.duration_s \
                == pytest.approx(dev.t_start + dev.duration_s)
            # ...and the assembly starts where the wake-up ends
            assert spans["rule-processing.assemble"].t_start \
                == pytest.approx(parent.t_start + parent.duration_s)
        busy = {name: metrics.counter(f"busy.{name}").value for name in (
            f"{SCORE}.enqueue", f"{SCORE}.readback",
            "rule-processing.assemble")}
        assert all(v > 0 for v in busy.values()), busy
        # the loop's own enqueue work is inside the stage's first part
        assert busy[f"{SCORE}.enqueue"] <= metrics.histogram(
            "scoring.device_enqueue_s").sum
        session.close()

    run(main())


def test_children_add_up_to_the_device_stage_in_the_pool(run):
    async def main():
        metrics = MetricsRegistry()
        pool = SharedScoringPool(
            build_model("lstm-stream", window=16, hidden=8), metrics,
            PoolConfig(batch_buckets=(32,), batch_window_ms=50.0))
        delivered: list = []

        async def deliver(scored):
            delivered.append(scored)

        slots = [pool.register(tid, TelemetryStore(history=32), 6.0, deliver)
                 for tid in ("a", "b")]
        await wait_until(lambda: pool.ready, timeout=120.0)
        takes = [[np.arange(8), np.arange(8)],
                 [np.array([0, 1, 0, 2, 0]), np.arange(4)],   # three rounds
                 [np.arange(8), np.arange(3)]]
        for k, take in enumerate(takes):
            for slot, tid, devices in zip(slots, "ab", take):
                slot.admit(_batch(devices, t=10.0 + k, tid=tid))
            pool._flush_round()
            await wait_until(lambda: len(delivered) == 2 * (k + 1),
                             timeout=60.0)
        _children_tile_the_stage(metrics, dispatches=3)
        assert metrics.counter(f"busy.{SCORE}.readback").value > 0
        assert metrics.counter("busy.rule-processing.assemble").value > 0
        pool.close()

    run(main())


def test_a_split_stage_is_counted_once_on_the_critical_path():
    tracer = Tracer(sample=1)
    t = 100.0
    tracer.record(1, "rule-processing.dispatch", "t", t, 0.001, 8)  # queue
    tracer.record(1, SCORE, "t", t, 0.010, 8)
    tracer.record(1, f"{SCORE}.enqueue", "t", t, 0.002, 8)
    tracer.record(1, f"{SCORE}.device", "t", t + 0.002, 0.005, 8)
    tracer.record(1, f"{SCORE}.wake", "t", t + 0.007, 0.003, 8)     # queue
    tracer.record(1, "egress.publish", "t", t + 0.011, 0.004, 8)
    local = tracer.critical_path()
    merged = merge_stage_exports([tracer.stage_export(),
                                  tracer.stage_export()])
    for cp in (local, merged):
        stages = cp["stages"]
        # the parent and `egress.publish` alone make the service sum, the
        # dispatch wait alone the queue sum: no child is added again
        assert cp["service_p99_ms"] == pytest.approx(
            stages[SCORE]["p99_ms"] + stages["egress.publish"]["p99_ms"])
        assert cp["queue_wait_p99_ms"] == pytest.approx(
            stages["rule-processing.dispatch"]["p99_ms"])
        # children are listed under their parent, in pipeline order
        names = list(stages)
        at = names.index(SCORE)
        assert names[at + 1:at + 4] == [
            f"{SCORE}.enqueue", f"{SCORE}.device", f"{SCORE}.wake"]
        assert all(stages[n]["parent"] == SCORE for n in names[at + 1:at + 4])
        assert "parent" not in stages[SCORE]
        assert stages[f"{SCORE}.wake"]["kind"] == "queue"
    assert merged["span_count"] == 2 * local["span_count"]


def test_span_adds_busy_seconds_and_records_the_sampled_span():
    metrics = MetricsRegistry()
    tracer = Tracer(sample=2, metrics=metrics)
    seconds = 0.0
    for trace_id in (1, 2):
        with tracer.span("event-sources.decode", trace_id, "t") as span:
            span.n_events = 7
        seconds += span.t_end - span.t_start
    busy = metrics.counter("busy.event-sources.decode")
    assert busy.value == pytest.approx(seconds) and seconds > 0
    recorded = tracer.spans(stage="event-sources.decode")
    assert [s.trace_id for s in recorded] == [2]
    assert recorded[0].n_events == 7 and recorded[0].tenant_id == "t"
    assert recorded[0].t_start == span.t_start
    assert recorded[0].duration_s == span.t_end - span.t_start
    # a body that raises is still counted, and the error passes through
    before = busy.value
    with pytest.raises(ValueError):
        with tracer.span("event-sources.decode"):
            raise ValueError("bad frame")
    assert busy.value > before
    # a tracer built without a registry keeps its own
    own = Tracer()
    with own.span("egress.publish"):
        pass
    assert own.metrics.counter("busy.egress.publish").value > 0


def test_trc01_holds_span_literals_to_the_registry():
    from sitewhere_tpu.analysis.checkers_trace import (
        check_trace_parity,
        check_trace_stages,
    )
    from sitewhere_tpu.analysis.engine import lint_sources

    def findings(source, checker, path="sitewhere_tpu/models/zscore.py"):
        report = lint_sources({path: source}, checkers=[checker])
        return [f.code for f in report.findings]

    typo = ("def f(self):\n"
            "    with self.tracer.span('rule-processing.asemble'):\n"
            "        pass\n")
    assert findings(typo, check_trace_stages) == ["TRC01"]
    computed = ("def f(self, name):\n"
                "    with self.tracer.span(name, 1):\n"
                "        pass\n")
    assert findings(computed, check_trace_stages) == ["TRC01"]
    good = ("def f(self):\n"
            "    with self.tracer.span('rule-processing.assemble', 1):\n"
            "        pass\n")
    assert findings(good, check_trace_stages) == []
    # a span on the path satisfies the parity contract as a record does
    hop = ("async def forward(self, record):\n"
           "    with self.tracer.span('egress.publish'):\n"
           "        self.bus.produce_nowait('t', record.value)\n")
    assert findings(hop, check_trace_parity,
                    "sitewhere_tpu/kernel/egresslane.py") == []


def test_collections_are_counted_while_watched():
    metrics = MetricsRegistry()
    tracer = Tracer(metrics=metrics)
    tracer.watch_gc()
    try:
        tracer.watch_gc()                       # installing twice is once
        assert gc.callbacks.count(tracer._on_gc) == 1
        gc.collect()
        gc.collect()
    finally:
        tracer.unwatch_gc()
    counted = metrics.counter("busy.gc").value
    assert counted > 0
    gc.collect()
    assert metrics.counter("busy.gc").value == counted
    assert tracer._on_gc not in gc.callbacks


def _thread_lines(trace_dir):
    """Host thread lines of the trace: {line name: {event names}}."""
    from benchmarks import xplane

    lines: dict[str, set] = {}
    for plane in xplane.load(xplane.find(trace_dir)):
        if plane["name"] == "/host:CPU":
            for line in plane["lines"]:
                lines.setdefault(line["name"], set()).update(
                    e[0] for e in line["events"])
    return lines


def test_spans_land_on_a_profile_on_the_thread_that_ran_them(run, tmp_path):
    """The benchmark's own profiler options (benchmarks/run.py
    `traced_slice`): host tracing at its lightest, Python tracing off."""

    async def main():
        metrics = MetricsRegistry()
        tracer = Tracer(metrics=metrics)
        session = ScoringSession(
            build_model("lstm-stream", window=16, hidden=8),
            TelemetryStore(history=32, initial_devices=64), metrics,
            ScoringConfig(buckets=(32,), threshold=4.0), tracer=tracer)
        session.warmup()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            tracer.watch_gc()
            for k in range(3):
                session.admit(_batch(np.arange(20), t=10.0 + k))
                await session.flush()
            gc.collect()
        finally:
            tracer.unwatch_gc()
            jax.profiler.stop_trace()
        session.close()

    run(main())
    lines = _thread_lines(str(tmp_path))
    loop_lines = {line for line, names in lines.items()
                  if f"{SCORE}.enqueue" in names}
    settle_lines = {line for line, names in lines.items()
                    if f"{SCORE}.readback" in names}
    # the settle pool names its OS threads, which is what a profile shows
    assert settle_lines and all(line.startswith("swx-settle")
                                for line in settle_lines)
    assert len(loop_lines) == 1 and not loop_lines & settle_lines
    assert {"rule-processing.assemble", "gc.gen2"} <= lines[loop_lines.pop()]


def test_ring_steps_carry_their_scopes_under_the_module_name_jit_step():
    from sitewhere_tpu.scoring.ring import DeviceRing, StackedDeviceRing
    from sitewhere_tpu.scoring.stream import (
        StreamingRing,
        streaming_step,
        streaming_step_sparse,
    )

    def lowered_text(fn, *args):
        lowered = fn.lower(*args)
        text = lowered.as_text(debug_info=True)
        return text, lowered.compile().as_text()

    model = build_model("lstm-stream", window=16, hidden=8)
    params = model.init(jax.random.PRNGKey(0))
    ring = StreamingRing(model, capacity=64)
    dev, v = np.arange(8, dtype=np.int32), np.zeros(8, np.float32)
    text, hlo = lowered_text(jax.jit(streaming_step(model)),
                             params, ring.state, dev, v)
    for scope in ("ring_gather", "cell_step", "ring_scatter"):
        assert scope in text, scope
    # benchmarks/xplane.py finds a step's runs as module `jit_step`
    assert "HloModule jit_step" in hlo
    text, hlo = lowered_text(
        jax.jit(streaming_step_sparse(model, 4, scratch_index=64)),
        params, ring.state, dev, v, np.float32(4.0))
    for scope in ("ring_gather", "cell_step", "ring_scatter", "sparse_topk"):
        assert scope in text, scope
    assert "HloModule jit_step" in hlo

    windowed = build_model("lstm", window=16, hidden=8)
    wparams = windowed.init(jax.random.PRNGKey(0))
    wring = DeviceRing(16, capacity=64)
    text, hlo = lowered_text(
        wring._build_update_score(windowed, 64, 8, prefer_fused=False),
        wparams, wring.values, wring.count, wring.cursor, dev, v)
    for scope in ("ring_scatter", "ring_gather", "window_score"):
        assert scope in text, scope
    assert "HloModule jit_step" in hlo
    stacked = StackedDeviceRing(16, n_tenants=2, device_cap=64)
    sparams = jax.tree.map(lambda leaf: np.stack([leaf, leaf]), wparams)
    text, _ = lowered_text(
        stacked._build_score(windowed), sparams, stacked.values,
        stacked.count, stacked.cursor, np.stack([dev, dev]),
        np.stack([v, v]))
    for scope in ("ring_scatter", "ring_gather", "window_score"):
        assert scope in text, scope
