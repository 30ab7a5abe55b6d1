"""The serving loop's account (kernel/tracing.py `Tracer.watch_loop`):
every task step credited to an operator, the selector's wait counted as
idle, the dark share and the stalls given a name.
"""

import ast
import asyncio
import json
import logging
import os
import shutil
import time

import pytest

from sitewhere_tpu.analysis.registry import (
    LOOP_NOT_OPERATORS,
    LOOP_OPERATORS,
    TRACE_STAGES,
)
from sitewhere_tpu.config import InstanceSettings
from sitewhere_tpu.kernel import tracing
from sitewhere_tpu.kernel.lifecycle import (
    BackgroundTaskComponent,
    LifecycleStatus,
    SupervisorPolicy,
)
from sitewhere_tpu.kernel.observe import observe_report
from sitewhere_tpu.kernel.service import ServiceRuntime
from sitewhere_tpu.kernel.tracing import Tracer, operator_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spin(seconds: float) -> None:
    """Keep the thread busy, as a blocking stretch of a task does."""
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


def counters(tracer: Tracer) -> dict:
    return {name: m.value for name, m in tracer.metrics._metrics.items()
            if name.startswith(("busy.", "loop.")) and hasattr(m, "value")}


# -- the identity --------------------------------------------------------------

def test_window_is_select_plus_busy_and_busy_is_operators_plus_callbacks(run):
    async def worker(n, busy_s, idle_s):
        for _ in range(n):
            spin(busy_s)
            await asyncio.sleep(idle_s)

    async def main():
        loop = asyncio.get_running_loop()
        tracer = Tracer()
        t0 = time.monotonic()
        tracer.watch_loop(loop)
        tasks = [loop.create_task(worker(40, 0.002, 0.004),
                                  name="instance-x/tenant-t0/fastlane"),
                 loop.create_task(worker(30, 0.003, 0.002),
                                  name="tenant-t0/egress-3"),
                 loop.create_task(worker(20, 0.001, 0.005))]
        loop.call_later(0.05, spin, 0.01)       # a callback that is no task
        await asyncio.gather(*tasks)
        tracer.unwatch_loop()
        return time.monotonic() - t0, counters(tracer)

    wall, c = run(main())
    # nothing left over: the loop either waited or was busy
    assert c["busy.loop"] + c["loop.select_s"] == pytest.approx(wall, rel=0.02)
    operators = sum(v for k, v in c.items() if k.startswith("busy.loop.")
                    and k != "busy.loop.unspanned")
    assert operators == pytest.approx(c["busy.loop"], rel=1e-9, abs=1e-9)
    # each task's known work is on its operator, and only there
    assert c["busy.loop.fastlane"] == pytest.approx(40 * 0.002, rel=0.25)
    assert c["busy.loop.egress"] == pytest.approx(30 * 0.003, rel=0.25)
    assert c["busy.loop.other"] == pytest.approx(20 * 0.001, rel=0.5)
    assert c["busy.loop.callbacks"] >= 0.01
    assert c["loop.select_s"] > 0.05
    # no span ran: every busy second is dark
    assert c["busy.loop.unspanned"] == pytest.approx(c["busy.loop"])


def test_a_span_inside_a_step_is_not_dark_and_one_on_another_thread_is(run):
    async def main():
        loop = asyncio.get_running_loop()
        tracer = Tracer()
        tracer.watch_loop(loop)

        def off_thread():
            with tracer.span("rule-processing.score.readback"):
                spin(0.03)

        async def persister():
            await asyncio.sleep(0)
            spin(0.02)                          # dark: between two awaits
            with tracer.span("event-management.persist"):
                spin(0.04)
                with tracer.span("event-sources.decode"):   # covered once
                    spin(0.01)
            await loop.run_in_executor(None, off_thread)

        await loop.create_task(persister(), name="tenant-t0/event-persister")
        tracer.unwatch_loop()
        return counters(tracer)

    c = run(main())
    assert c["busy.loop.event-persister"] == pytest.approx(0.07, rel=0.2)
    assert c["busy.event-management.persist"] == pytest.approx(0.05, rel=0.2)
    assert c["busy.rule-processing.score.readback"] >= 0.03
    named = c["busy.loop"] - c["busy.loop.unspanned"]
    # the outermost span on the loop's thread, once; the other thread's not
    assert named == pytest.approx(c["busy.event-management.persist"],
                                  rel=0.02)
    assert c["busy.loop.unspanned"] >= 0.02


def test_a_collection_on_the_loops_thread_is_covered_once(run):
    async def main():
        import gc

        loop = asyncio.get_running_loop()
        tracer = Tracer()
        tracer.watch_gc()
        tracer.watch_loop(loop)
        try:
            async def collector():
                await asyncio.sleep(0)
                gc.collect()                    # outside every span
                with tracer.span("event-management.persist"):
                    gc.collect()                # inside one: the span's
            await loop.create_task(collector(), name="t/event-persister")
        finally:
            tracer.unwatch_loop()
            tracer.unwatch_gc()
        return counters(tracer)

    c = run(main())
    named = c["busy.loop"] - c["busy.loop.unspanned"]
    assert c["busy.gc"] > 0
    assert 0 < named < c["busy.gc"] + c["busy.event-management.persist"]
    assert named >= c["busy.event-management.persist"]


# -- the naming rule -----------------------------------------------------------

@pytest.mark.parametrize("task_name, operator", [
    # a component's path (BackgroundTaskComponent._spawn)
    ("instance-bench/event-sources/tenant-t0/fastlane", "fastlane"),
    ("tenant-t0/fastlane", "fastlane"),
    ("instance-x/telemetry-beat", "telemetry-beat"),
    ("instance-x/rule-processing/tenant-t0/rule-processor", "rule-processor"),
    ("instance-x/device-state/tenant-acme/state-merger", "state-merger"),
    # shards, ports and consumer ids: a trailing -<digits> is dropped
    ("tenant-t0/egress-3", "egress"),
    ("tenant-t0/fastlane-1", "fastlane"),
    ("tenant-t0/event-persister-2", "event-persister"),
    ("wire-rx-47810", "wire-rx"),
    ("wire-push-12", "wire-push"),
    # a component the deployment names carries its class's operator last
    ("instance-x/event-sources/tenant-t0/gw/tcp-receiver", "tcp-receiver"),
    ("instance-x/device-management/registry-snapshotter/snapshotter",
     "snapshotter"),
    # the rightmost element that fits wins; what fits nothing is skipped
    ("scoring-pool/lstm-stream", "scoring-pool"),
    ("scoring-pool/lstm-stream/warmup", "warmup"),
    ("instance-x/fleet-controller/loop", "fleet-controller"),
    ("instance-x/tenant-t0/event-persister/supervisor", "supervisor"),
    # the tasks that used to be Task-<n>
    ("scoring-settle", "scoring-settle"),
    ("tcp-receiver", "tcp-receiver"),
    ("fastlane-produce", "fastlane-produce"),
    # no name the code gave: other, whatever the fleet calls itself
    ("Task-17", "other"),
    ("tenant-fastlane", "other"),
    ("tenant-t0/gw", "other"),
    ("instance-egress-3/tenant-callbacks/unspanned", "other"),
    ("", "other"),
])
def test_operator_of_a_task_name(task_name, operator):
    assert operator_of(task_name) == operator
    assert operator in LOOP_OPERATORS or operator == "other"


def test_no_tenant_or_instance_id_reaches_a_counters_name(run):
    async def main():
        rt = ServiceRuntime(InstanceSettings(instance_id="plant-7"))
        await rt.start()
        try:
            for name in ("tenant-acme/fastlane", "tenant-acme/gw",
                         "plant-7/tenant-acme/egress-2", "acme"):
                await asyncio.get_running_loop().create_task(
                    asyncio.sleep(0), name=name)
            await asyncio.sleep(0.3)            # a beat or two
        finally:
            await rt.stop()
        return counters(rt.tracer)

    names = [n for n in run(main()) if n.startswith("busy.loop")]
    assert {"busy.loop", "busy.loop.fastlane", "busy.loop.egress",
            "busy.loop.other", "busy.loop.telemetry-beat",
            "busy.loop.callbacks", "busy.loop.unspanned"} <= set(names)
    allowed = LOOP_OPERATORS | LOOP_NOT_OPERATORS
    assert all(n == "busy.loop" or n[len("busy.loop."):] in allowed
               for n in names)
    assert not any("acme" in n or "plant" in n for n in names)


# -- the wrapper is transparent -------------------------------------------------

def test_a_watched_task_reads_as_before(run):
    async def main():
        loop = asyncio.get_running_loop()
        tracer = Tracer()
        tracer.watch_loop(loop)
        seen = {}

        async def answer():
            await asyncio.sleep(0)
            return 42

        async def fail():
            await asyncio.sleep(0)
            raise KeyError("boom")

        async def waits():
            try:
                await asyncio.sleep(30)
            except asyncio.CancelledError:
                seen["cancelled"] = True
                raise

        async def catches():
            try:
                await asyncio.sleep(30)
            except ValueError as exc:
                return f"caught {exc}"

        try:
            t = loop.create_task(waits(), name="tenant-t0/fastlane")
            await asyncio.sleep(0.01)
            seen["repr"] = repr(t)
            seen["stack"] = [f.f_code.co_name for f in t.get_stack()]
            coro = t.get_coro()
            seen["names"] = (coro.__name__, coro.__qualname__,
                             coro.cr_code.co_name, coro.cr_running,
                             coro.cr_frame is not None,
                             coro.cr_await is not None)
            t.cancel()
            with pytest.raises(asyncio.CancelledError):
                await t
            seen["result"] = await loop.create_task(answer())
            with pytest.raises(KeyError, match="boom"):
                await loop.create_task(fail())
            # throw: through the wrapper, into the coroutine's frame
            t = loop.create_task(catches())
            await asyncio.sleep(0.01)
            with pytest.raises(StopIteration) as done:
                t.get_coro().throw(ValueError("thrown"))
            seen["thrown"] = done.value.value
            t.cancel()
            # close: a coroutine that never ran is closed, not leaked
            never = tracing._TaskSteps(answer(), tracer._watch)
            never.close()
            seen["closed"] = never.cr_frame is None
            seen["iscoroutine"] = asyncio.iscoroutine(never)
        finally:
            tracer.unwatch_loop()
        return seen

    seen = run(main())
    assert seen["cancelled"] and seen["result"] == 42
    assert "name='tenant-t0/fastlane'" in seen["repr"]
    assert "waits() running at" in seen["repr"] and __file__ in seen["repr"]
    assert seen["stack"] == ["waits"]
    assert seen["names"] == ("waits", seen["names"][1], "waits", False,
                             True, True)
    assert seen["names"][1].endswith("main.<locals>.waits")
    assert seen["thrown"] == "caught thrown"
    assert seen["closed"] and seen["iscoroutine"]


def test_a_crashed_component_is_restarted_by_its_supervisor_under_the_factory(
        run, caplog):
    class Flaky(BackgroundTaskComponent):
        def __init__(self):
            super().__init__("event-persister", SupervisorPolicy(
                max_restarts=3, base_backoff_s=0.01))
            self.runs = 0

        async def _run(self):
            self.runs += 1
            await asyncio.sleep(0)
            if self.runs == 1:
                raise RuntimeError("first run dies")
            spin(0.005)
            await asyncio.Event().wait()

    async def main():
        tracer = Tracer()
        tracer.watch_loop(asyncio.get_running_loop())
        comp = Flaky()
        try:
            await comp.start()
            for _ in range(200):
                if comp.runs == 2:
                    break
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.02)
            name = comp._task.get_name()
            await comp.stop()
        finally:
            tracer.unwatch_loop()
        return comp, name, counters(tracer)

    with caplog.at_level(logging.WARNING):
        comp, name, c = run(main())
    assert comp.runs == 2 and comp.restart_count == 1
    assert comp.status is LifecycleStatus.STOPPED
    assert isinstance(comp.last_crash, RuntimeError)
    assert name == "event-persister"
    # the crash log reads as before: the path, the exception, its frame
    crash = next(r for r in caplog.records if "crashed" in r.getMessage())
    assert "event-persister crashed (RuntimeError: first run dies)" \
        in crash.getMessage()
    assert crash.exc_info[2].tb_frame.f_code.co_name in ("_run", "send")
    assert c["busy.loop.event-persister"] >= 0.005
    assert c["busy.loop.supervisor"] > 0


# -- stalls ----------------------------------------------------------------------

def test_a_stall_is_in_the_ring_in_the_report_and_in_the_beats_warning(
        run, caplog):
    async def main():
        rt = ServiceRuntime(InstanceSettings(
            instance_id="stall", observe_stall_ms=100.0,
            observe_interval_ms=50.0))
        await rt.start()
        try:
            async def persister():
                await asyncio.sleep(0)
                with rt.tracer.span("event-management.persist"):
                    spin(0.12)
                spin(0.03)

            await asyncio.get_running_loop().create_task(
                persister(), name="instance-stall/tenant-t0/event-persister")
            await asyncio.sleep(0.2)            # the beat wakes late, warns
            report = observe_report(rt)
            slow = rt.tracer.slow_steps()
            snap = counters(rt.tracer)
            hist = rt.metrics._metrics["loop.long_step_s"]
        finally:
            await rt.stop()
        return report, slow, snap, hist

    with caplog.at_level(logging.WARNING):
        report, slow, c, hist = run(main())
    step = next(s for s in slow if s["operator"] == "event-persister")
    assert step["task"] == "instance-stall/tenant-t0/event-persister"
    assert 0.15 <= step["seconds"] < 0.5
    assert step["stage"] == "event-management.persist"
    assert 0.12 <= step["stage_s"] <= step["seconds"]
    assert c["loop.slow_steps"] >= 1 and hist.count >= 1
    assert hist._max == pytest.approx(max(s["seconds"] for s in slow))
    # GET /api/instance/observe and `swx top` read the same ring
    listed = report["loop"]["slow_steps"]
    assert any(s["operator"] == "event-persister"
               and s["stage"] == "event-management.persist" for s in listed)
    assert report["loop"]["operators"]["event-persister"]["steps"] >= 2
    assert json.dumps(report["loop"])
    from sitewhere_tpu.cli import render_top

    screen = render_top(report)
    assert "slow steps" in screen and "event-persister" in screen
    assert "in event-management.persist" in screen
    # the beat names who held the loop
    warning = next(r.getMessage() for r in caplog.records
                   if "event loop lagged" in r.getMessage())
    assert "task instance-stall/tenant-t0/event-persister " \
        "(operator event-persister) held the loop" in warning
    assert "in event-management.persist" in warning
    assert "not yielding" not in warning


def test_short_steps_pay_no_histogram(run):
    async def main():
        tracer = Tracer()
        tracer.watch_loop(asyncio.get_running_loop())

        async def quick():
            for _ in range(50):
                await asyncio.sleep(0)

        await asyncio.get_running_loop().create_task(quick())
        tracer.unwatch_loop()
        return tracer

    tracer = run(main())
    assert tracer.metrics._metrics["loop.long_step_s"].count == 0
    assert tracer.slow_steps() == []


# -- watching and unwatching leave the loop as found -----------------------------

def test_watch_twice_and_unwatch_leave_factory_and_selector_as_found(run):
    async def main():
        loop = asyncio.get_running_loop()
        made = []

        def mine(loop, coro, **kw):
            made.append(coro)
            return asyncio.Task(coro, loop=loop, **kw)

        loop.set_task_factory(mine)
        selector = loop._selector
        tracer = Tracer()
        tracer.watch_loop(loop)
        watched = loop.get_task_factory(), loop._selector
        tracer.watch_loop(loop)                 # twice is once
        assert (loop.get_task_factory(), loop._selector) == watched
        assert watched[0] is not mine and watched[1] is not selector
        await loop.create_task(asyncio.sleep(0), name="t/fastlane")
        assert isinstance(made[-1], tracing._TaskSteps)     # chained
        tracer.unwatch_loop()
        assert loop.get_task_factory() is mine and loop._selector is selector
        tracer.unwatch_loop()                   # and again: nothing
        await loop.create_task(asyncio.sleep(0))
        assert not isinstance(made[-1], tracing._TaskSteps)
        loop.set_task_factory(None)
        # two runtimes on one loop that stop in the order they started
        first, second = Tracer(), Tracer()
        first.watch_loop(loop)
        second.watch_loop(loop)
        task = loop.create_task(asyncio.sleep(0.01), name="t/egress")
        first.unwatch_loop()
        await task
        second.unwatch_loop()
        assert loop.get_task_factory() is None and loop._selector is selector
        return counters(first), counters(second), counters(tracer)

    first, second, c = run(main())
    assert c["busy.loop.fastlane"] > 0
    assert second["busy.loop.egress"] > 0 and second["loop.select_s"] >= 0.01
    # a watch that ended stops counting, though its wrapper lives on
    assert first["loop.select_s"] < 0.01


def test_the_seam_is_where_this_interpreter_waits(run):
    """`watch_loop` times the selector through `loop._selector`: the one
    place asyncio's selector loops wait, once an iteration."""
    import asyncio.selector_events
    import inspect

    source = inspect.getsource(asyncio.base_events.BaseEventLoop._run_once)
    assert source.count("self._selector.select(timeout)") == 1

    async def main():
        loop = asyncio.get_running_loop()
        assert isinstance(loop, asyncio.selector_events.BaseSelectorEventLoop)
        assert tracing._selector_of(loop) is loop._selector
        tracer = Tracer()
        tracer.watch_loop(loop)
        assert tracer._watch.selector is loop._selector
        await asyncio.sleep(0.02)
        tracer.unwatch_loop()
        return counters(tracer)

    assert run(main())["loop.select_s"] >= 0.015
    # the annotation class says whether a trace runs, so an untraced
    # step pays no annotation
    from jax.profiler import TraceAnnotation

    assert TraceAnnotation.is_enabled() is False


def test_the_runtime_starts_on_a_loop_without_the_seam(run, monkeypatch):
    monkeypatch.setattr(tracing, "_selector_of", lambda loop: None)

    async def main():
        loop = asyncio.get_running_loop()
        selector = loop._selector
        rt = ServiceRuntime(InstanceSettings(instance_id="seamless"))
        await rt.start()
        try:
            assert loop._selector is selector
            async def work():
                await asyncio.sleep(0)
                spin(0.01)
            await loop.create_task(work(), name="tenant-t0/fastlane")
            await asyncio.sleep(0.15)
            report = observe_report(rt)["loop"]
        finally:
            await rt.stop()
        return report, counters(rt.tracer)

    report, c = run(main())
    assert c["busy.loop.fastlane"] >= 0.01      # steps are still counted
    assert report["operators"]["fastlane"]["steps"] == 2
    assert c["busy.loop"] == 0 and c["loop.select_s"] == 0
    assert report["busy_s"] is None and report["select_s"] is None
    assert c["loop.slow_steps"] == 0            # an idle wait is no stall


def test_steps_are_annotations_only_while_a_trace_runs(run):
    class Fake:
        live = False
        seen: list = []

        def __init__(self, name):
            self.name = name

        def __enter__(self):
            Fake.seen.append(self.name)

        def __exit__(self, *exc):
            pass

        @staticmethod
        def is_enabled():
            return Fake.live

    async def main():
        loop = asyncio.get_running_loop()
        tracer = Tracer()
        tracer._annotation_cls = Fake
        tracer.watch_loop(loop)
        try:
            await loop.create_task(asyncio.sleep(0.001), name="t/fastlane")
            assert Fake.seen == []
            Fake.live = True
            async def handler():
                asyncio.current_task().set_name("tcp-receiver")
                await asyncio.sleep(0.001)
                with tracer.span("event-sources.decode"):
                    pass
            await loop.create_task(handler())
        finally:
            Fake.live = False
            tracer.unwatch_loop()

    run(main())
    # a handler names itself inside its first step: that one annotation
    # reads `other`, every later one and every second its operator
    assert Fake.seen.count("loop.tcp-receiver") == 1
    assert Fake.seen.index("loop.other") < Fake.seen.index("loop.tcp-receiver")
    assert "loop.select" in Fake.seen and "loop.fastlane" not in Fake.seen
    assert Fake.seen.index("loop.tcp-receiver") \
        < Fake.seen.index("event-sources.decode")


# -- the inventory against the tree ----------------------------------------------

def _literal_parts(node: ast.expr):
    """The string a name expression gives, with each computed part as
    `{}`; None where the expression is no string the code spells out."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.JoinedStr):
        return ["".join(p.value if isinstance(p, ast.Constant) else "{}"
                        for p in node.values)]
    if isinstance(node, ast.IfExp):
        a, b = _literal_parts(node.body), _literal_parts(node.orelse)
        return a + b if a and b else None
    return None


def _named(name: str) -> bool:
    """Does this name give its task an operator of the inventory
    whatever its computed parts turn out to be? One may stand for digits
    (`egress-{index}`) or for a whole element that the rule then skips
    (`{self.path}/warmup`, `scoring-pool/{model}`), never for the
    operator itself."""
    return operator_of(name.replace("-{}", "-0").replace("{}", "?")) \
        != "other"


def _tree():
    for folder, _dirs, files in os.walk(os.path.join(ROOT, "sitewhere_tpu")):
        for name in files:
            if name.endswith(".py") and "analysis" not in folder:
                path = os.path.join(folder, name)
                with open(path) as fh:
                    yield os.path.relpath(path, ROOT), ast.parse(fh.read())


def test_every_task_of_the_tree_has_an_operator_of_the_inventory():
    classes: dict[str, ast.ClassDef] = {}
    bases: dict[str, list[str]] = {}
    spawns, handlers, servers = [], {}, []
    for path, tree in _tree():
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                classes[node.name] = node
                bases[node.name] = [b.id if isinstance(b, ast.Name)
                                    else getattr(b, "attr", "")
                                    for b in node.bases]
            elif isinstance(node, ast.AsyncFunctionDef):
                handlers.setdefault((path, node.name), node)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute):
                if node.func.attr in ("create_task", "ensure_future"):
                    spawns.append((path, node))
                elif node.func.attr == "start_server":
                    servers.append((path, node))

    def is_background(name: str) -> bool:
        return name == "BackgroundTaskComponent" or any(
            is_background(b) for b in bases.get(name, ()))

    def declared(name: str):
        """`operator = "<name>"` on the class, or on one it extends."""
        if name not in classes:
            return None
        for stmt in classes[name].body:
            if isinstance(stmt, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "operator"
                    for t in stmt.targets):
                return stmt.value.value
        return next(filter(None, map(declared, bases[name])), None)

    def own_name(name: str):
        """What the class hands `super().__init__` as its name."""
        if name not in classes:
            return None
        for call in ast.walk(classes[name]):
            if isinstance(call, ast.Call) and call.args \
                    and isinstance(call.func, ast.Attribute) \
                    and call.func.attr == "__init__":
                return _literal_parts(call.args[0])
        return next(filter(None, map(own_name, bases[name])), None)

    # every component that owns a task (the classes nothing extends): the
    # word its class declares, else the name its code spells out
    extended = {b for bs in bases.values() for b in bs}
    components = [n for n in classes if n not in extended and n not in (
        "BackgroundTaskComponent", "SupervisedTaskComponent")   # the bases
        and is_background(n)]
    assert len(components) >= 26
    for name in components:
        operator = declared(name)
        if operator is not None:
            assert operator in LOOP_OPERATORS, (name, operator)
            continue
        parts = own_name(name)
        assert parts, f"{name}: named by its caller, declares no operator"
        assert all(_named(p) for p in parts), (name, parts)

    # every create_task of the tree is named where it is made
    assert len(spawns) >= 18
    for path, call in spawns:
        name = next((k.value for k in call.keywords if k.arg == "name"), None)
        assert name is not None, f"{path}:{call.lineno}: a task with no name"
        parts = _literal_parts(name)
        if parts is None:                       # BackgroundTaskComponent's
            assert path.endswith("kernel/lifecycle.py"), (path, call.lineno)
            continue
        assert all(_named(p) for p in parts), (path, call.lineno, parts)

    # every connection handler asyncio makes names itself at its first line
    assert len(servers) >= 8
    for path, call in servers:
        handler = handlers[(path, call.args[0].attr)]
        first = next(s for s in handler.body
                     if not (isinstance(s, ast.Expr)
                             and isinstance(s.value, ast.Constant)))
        assert isinstance(first, ast.Expr) \
            and isinstance(first.value, ast.Call) \
            and first.value.func.attr == "set_name", (path, handler.name)
        assert first.value.args[0].value in LOOP_OPERATORS, (path,
                                                             handler.name)


def test_operators_are_no_stages():
    """An operator is never `record()`ed and has no place in the critical
    path; merged into TRACE_STAGES it would outbid every stage where a
    gap is named by the span that covers most of it."""
    stages = {name for name, _kind in TRACE_STAGES}
    assert not stages & (LOOP_OPERATORS | LOOP_NOT_OPERATORS)
    assert not any(s.startswith("loop.") for s in stages)
    assert not hasattr(Tracer, "stages")        # unread since PR 26: gone


# -- the served path --------------------------------------------------------------

def test_the_saturated_cell_tiny_on_cpu_accounts_for_its_window(
        tmp_path, monkeypatch):
    """`stream-512k.saturate` cut to 64 devices through the benchmark's
    own `run_cell`: over the window the loop either waited or was busy,
    and the served path's four operators each did some of the work."""
    import jax
    from concurrent.futures import ThreadPoolExecutor

    from benchmarks import run as bench
    from sitewhere_tpu.scoring import pool, server

    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    path = tmp_path / "benchmarks" / "configs" / "stream-512k.json"
    cfg = json.loads(path.read_text())
    cfg.update(devices_per_tenant=64, frame_devices=16, anomaly_rate=0.02)
    path.write_text(json.dumps(cfg))
    # as tests/benchmarks/test_bench_run.py: the cache placed from
    # outside, the two thresholds put back, one settle thread (on the CPU
    # the order of two read-backs side by side is a race)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    one = ThreadPoolExecutor(max_workers=1, thread_name_prefix="settle-1")
    monkeypatch.setattr(server, "SETTLE_POOL", one)
    monkeypatch.setattr(pool, "SETTLE_POOL", one)
    seen = {}
    per_layer = bench.per_layer

    def keep(cell, obs):
        seen["counters"] = obs["window_metrics"]["counters"]
        seen["seconds"] = obs["end"] - obs["start"]
        return per_layer(cell, obs)

    monkeypatch.setattr(bench, "per_layer", keep)
    try:
        result, info = bench.run_cell("stream-512k.saturate", 2 ** 31 + 37,
                                      2.0, True, "cpu", root=str(tmp_path))
    finally:
        one.shutdown(wait=False)
        for k, v in before.items():
            jax.config.update(k, v)
    assert result["correct"], result["checks"]
    assert info["frames"] > 10
    c, seconds = seen["counters"], seen["seconds"]
    # the window's edges fall inside a stretch each: a few per cent
    assert c["busy.loop"] + c["loop.select_s"] == pytest.approx(seconds,
                                                                rel=0.1)
    parts = sum(v for k, v in c.items() if k.startswith("busy.loop.")
                and k != "busy.loop.unspanned")
    assert parts == pytest.approx(c["busy.loop"], rel=0.05)
    for operator in ("fastlane", "scoring-settle", "event-persister",
                     "tcp-receiver"):
        assert c[f"busy.loop.{operator}"] > 0, operator
    assert 0 < c["busy.loop.unspanned"] < c["busy.loop"]
    # what the spans of PR 26 name is inside what the loop was busy with
    spans = sum(c.get(f"busy.{s}", 0.0) for s in (
        "event-sources.decode", "event-management.persist",
        "device-state.merge", "rule-processing.score.enqueue",
        "rule-processing.assemble", "egress.publish"))
    assert spans == pytest.approx(c["busy.loop"] - c["busy.loop.unspanned"],
                                  rel=0.25)
    assert set(c) >= {"busy.loop.callbacks", "loop.slow_steps"}
