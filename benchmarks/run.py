"""The benchmark's command: one cell, one run, one result line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip. It builds the runtime as `swx run` does,
creates the configuration's tenants, registers and seeds their fleets,
installs weights made from the seed, waits for warm-up, and starts one
feeder child (benchmarks/feeder.py, no JAX) against the tenants' TCP
gateways. It watches `scored-events` on its own clock, measures for
`--seconds`, drains, stops everything, and only then runs the plain
reference (the model's file under benchmarks/models/) over the same
history and frames and compares every served score. The last line of
stdout is the result.

Everything that belongs to one cell is data: BENCHMARK.json names the
cell's configuration and traffic, `configs/<config>.json` and
`traffic/<cell>.json` hold them, the configuration's `model` names its
reference and counts under `models/`, `metrics/<name>.json` names each
per-layer metric's reader under `readers/`. The flow from the runtime to
the counts is a copy of chip_smoke.py's phases A and C.

`run_cell` is the body; tests/benchmarks runs it tiny on the CPU.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()      # as near to process start as Python lets us

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import compare, gen, models, xplane  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
GATEWAY = "gw"
WARM_DEADLINE_S = 600.0
WARM_BEAT_S = 5.0
DRAIN_DEADLINE_S = 60.0          # a late answer is late, not wrong
DRAIN_STALL_S = 10.0             # ...but one that never comes is given up on
TRACE_START, TRACE_SHARE, TRACE_MAX_S = 1 / 3, 1 / 3, 3.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


class Cell:
    """One workload of BENCHMARK.json with the files it names."""

    def __init__(self, root: str, name: str):
        self.root = root
        self.manifest = m = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in m["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.name, self.spec = name, cells[name]
        conf = {c["name"]: c for c in m["configs"]}[self.spec["config"]]
        self.config = load_json(root, conf["file"])
        self.data_dir = os.path.dirname(os.path.dirname(
            os.path.join(root, conf["file"])))
        self.traffic = load_json(self.data_dir, "traffic", f"{name}.json")
        self.chips = int(self.spec["chips"])

    @property
    def model(self):
        """The configuration's model: its plain reference and counts
        (benchmarks/models/), imported when first asked for (it uses JAX)."""
        return models.load(self.config["model"])

    def reports(self, metric: dict) -> bool:
        """Does this cell report `metric`? One with no `workloads` key is
        reported wherever the end-to-end metric it moves is (an
        end-to-end metric without the key: everywhere)."""
        if "workloads" in metric:
            return self.name in metric["workloads"]
        moved = [e for e in self.manifest["end_to_end"]
                 if e["name"] == metric.get("moves")]
        return self.reports(moved[0]) if moved else True

    def metrics(self, kind: str) -> list[dict]:
        return [m for m in self.manifest[kind] if self.reports(m)]


class CompileCounter:
    """Counts XLA executables this process builds or loads (JAX's
    backend-compile event fires for persistent-cache hits too). JAX has
    no public unregister, so `close()` makes the listener inert."""

    def __init__(self):
        import jax

        self.compiles = 0
        self._live = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if self._live and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def close(self) -> None:
        self._live = False


# -- set-up: runtime, tenants, fleets, weights (after chip_smoke.py) ----------

def tenant_sections(cfg: dict) -> dict:
    devices = int(cfg["devices_per_tenant"])
    rule = {"model": cfg["model"],
            "model_config": dict(cfg["model_config"]),
            "threshold": cfg["threshold"],
            # the ring holds the fleet, a flush is one gateway's frame
            "buckets": [int(cfg.get("frame_devices") or devices)],
            "capacity": devices,
            **cfg.get("engine", {})}
    if cfg.get("mesh"):
        rule["mesh"] = dict(cfg["mesh"])
    return {"rule-processing": rule,
            "event-sources": {"receivers": [
                {"kind": "tcp", "decoder": "swb1", "name": GATEWAY,
                 "port": 0}]}}


async def start_runtime(instance_id: str):
    """The runtime `swx run` builds: every service, REST on an ephemeral
    port, default settings."""
    from sitewhere_tpu.cli import _build_runtime
    from sitewhere_tpu.config import InstanceSettings

    rt = _build_runtime(InstanceSettings(instance_id=instance_id,
                                         rest_port=0), [])
    await rt.start()
    return rt


def seed_history(rt, tenant_id: str, fleet: gen.Fleet, ticks: int) -> np.ndarray:
    """Register the fleet and put `ticks` clean ticks straight into the
    host store (set-up, not traffic), so the scorer's short-history gate
    is open from the first frame. Returns them, [devices, ticks]. With
    `ticks` 0 the fleet starts cold: the store stays empty, the ring keeps
    its zero state, and the warm-up beats build every device's state
    through the served path."""
    from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
    from sitewhere_tpu.domain.model import DeviceType

    rt.api("device-management").management(tenant_id).bootstrap_fleet(
        DeviceType(token="thermo", name="Thermometer"), fleet.devices)
    em = rt.api("event-management").management(tenant_id)
    hist = np.empty((fleet.devices, ticks), np.float32)
    mtype = np.zeros(fleet.devices, np.uint16)
    for k in range(ticks):
        hist[:, k] = v = fleet.values(k, spikes=False)
        em.telemetry.append_measurements(MeasurementBatch(
            BatchContext(tenant_id=tenant_id, source="benchmark-seed"),
            fleet.device_index, mtype, v,
            np.full(fleet.devices, k * gen.TICK_S, np.float64)))
    return hist


async def wait_warm(sink, what: str) -> None:
    t0 = time.monotonic()
    while not sink.ready:
        if time.monotonic() - t0 > WARM_DEADLINE_S:
            raise TimeoutError(f"{what} not done in {WARM_DEADLINE_S:.0f}s; "
                               f"last warm-up error: {sink.warmup_error!r}")
        await asyncio.sleep(0.02)


def snapshot_metrics(rt) -> dict:
    """Raw counters and histogram counts of the program's registry."""
    from sitewhere_tpu.kernel.metrics import Counter, Histogram

    snap = {"counters": {}, "histograms": {}}
    for name, m in rt.metrics._metrics.items():
        if isinstance(m, Counter):
            snap["counters"][name] = float(m.value)
        elif isinstance(m, Histogram):
            snap["histograms"][name] = {
                "buckets": list(m.buckets), "counts": list(m.counts),
                "max": float(m._max)}
    return snap


def metrics_delta(a: dict, b: dict) -> dict:
    """What the window added: b minus a."""
    out = {"counters": {}, "histograms": {}}
    for name, v in b["counters"].items():
        out["counters"][name] = v - a["counters"].get(name, 0.0)
    for name, h in b["histograms"].items():
        h0 = a["histograms"].get(name)
        base = h0["counts"] if h0 else [0] * len(h["counts"])
        out["histograms"][name] = {
            "buckets": h["buckets"], "max": h["max"],
            "counts": [x - y for x, y in zip(h["counts"], base)]}
    return out


class Feeder:
    """The generator child and its line protocol (see feeder.py)."""

    def __init__(self, proc):
        self.proc = proc

    @classmethod
    async def start(cls, cfg: dict) -> "Feeder":
        # JAX_PLATFORMS=cpu in the CHILD's environment only: even an
        # accidental jax import there could not reach for the chip
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "feeder.py"), json.dumps(cfg),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            env=env, limit=1 << 26)
        self = cls(proc)
        assert await self.line(60.0) == "READY"
        return self

    def tell(self, text: str) -> None:
        self.proc.stdin.write(text.encode() + b"\n")

    async def line(self, timeout: float) -> str:
        raw = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if not raw:
            raise RuntimeError(f"feeder exited ({self.proc.returncode}) "
                               "without answering")
        return raw.decode().strip()

    async def stop(self) -> None:
        """Every process this harness starts is stopped before it returns."""
        if self.proc.returncode is None:
            try:
                self.tell("Q")
                await asyncio.wait_for(self.proc.wait(), 5.0)
            except (asyncio.TimeoutError, ConnectionError, RuntimeError):
                self.proc.kill()
                await self.proc.wait()


class Collector:
    """Watches the tenants' `scored-events` topics on this process's
    clock, keeps what was published, and (closed loop) hands the feeder
    one credit for each whole beat (a frame of every tenant) seen."""

    def __init__(self, rt, tenant_ids: list[str], frame_devices: int):
        topics = [rt.naming.tenant_topic(t, "scored-events")
                  for t in tenant_ids]
        self.tenant_of = {topic: i for i, topic in enumerate(topics)}
        self.consumer = rt.bus.subscribe(topics, group="benchmark")
        self.frame_devices = frame_devices
        self.records: list[tuple] = []      # (seen, tenant, ScoredBatch)
        self.events = [0] * len(tenant_ids)
        self.credit_to: Feeder | None = None
        self._credited = 0
        self._task = asyncio.create_task(self._run())

    def beats_done(self) -> int:
        return min(self.events) // self.frame_devices

    def total(self) -> int:
        return sum(self.events)

    def start_credits(self, feeder: Feeder) -> None:
        self._credited = self.beats_done()
        self.credit_to = feeder

    async def _run(self) -> None:
        while True:
            records = await self.consumer.poll(max_records=512, timeout=0.25)
            if not records:
                continue
            now = time.monotonic()
            for r in records:
                tenant = self.tenant_of[r.topic]
                self.records.append((now, tenant, r.value))
                self.events[tenant] += len(r.value)
            if self.credit_to is not None:
                done = self.beats_done()
                if done > self._credited:
                    self.credit_to.proc.stdin.write(
                        b"C\n" * (done - self._credited))
                    self._credited = done

    async def close(self) -> None:
        self.credit_to = None
        self._task.cancel()
        try:
            await self._task
        except asyncio.CancelledError:
            pass
        self.consumer.close()


class MemoryWatch:
    """What the process holds of the fullest chip's memory. The runtime
    counts it in two parts: arrays (`bytes_in_use`) and the scratch that
    compiled programs run in (`bytes_reserved`), which it sizes for the
    largest program run so far and keeps. Both are memory no one else can
    have, so a chip's share is their sum, read at one instant; the peak
    is the largest such reading (at every lap of set-up, when the window
    closes, after the drain), and never less than the arrays' own peak.
    A backend that reports nothing (the CPU) reads 0."""

    def __init__(self):
        self.held = self.in_use_peak = self.reserved_peak = 0

    def sample(self) -> None:
        import jax

        for d in jax.local_devices():
            st = d.memory_stats() or {}
            in_use = int(st.get("bytes_in_use", 0))
            reserved = int(st.get("bytes_reserved", 0))
            self.held = max(self.held, in_use + reserved)
            self.in_use_peak = max(self.in_use_peak,
                                   int(st.get("peak_bytes_in_use", in_use)))
            self.reserved_peak = max(self.reserved_peak, int(
                st.get("peak_bytes_reserved", reserved)))

    def report(self) -> dict:
        return {"memory_peak_bytes": max(self.held, self.in_use_peak),
                "memory_arrays_peak_bytes": self.in_use_peak,
                "memory_scratch_peak_bytes": self.reserved_peak}


async def traced_slice(trace_dir: str, start: float, length: float) -> dict:
    """Profile [start, start + length) with jax.profiler, off the event
    loop, host tracing at its lightest: only this process can trace the
    chip, and the tracer shares its cores with the server."""
    import jax

    loop = asyncio.get_running_loop()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    await asyncio.sleep(max(start - time.monotonic(), 0.0))
    await loop.run_in_executor(
        None, lambda: jax.profiler.start_trace(trace_dir,
                                               profiler_options=opts))
    t0 = time.monotonic()
    await asyncio.sleep(length)
    t1 = time.monotonic()
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    return {"t0": t0, "t1": t1}


# -- the run ------------------------------------------------------------------

async def serve_and_measure(cell: Cell, seed: int, seconds: float,
                            trace: bool, t_process: float,
                            compiles: CompileCounter) -> dict:
    """Set-up, window and drain. Returns what was observed; holds no
    reference to the runtime when it returns."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    n_tenants, devices = int(cfg["tenants"]), int(cfg["devices_per_tenant"])
    tenant_ids = [f"t{i}" for i in range(n_tenants)]
    hist_ticks = int(cfg["history_ticks"])
    warm_beats = int(traffic.get("warm_beats", 4))
    fleets = gen.fleets(cfg, seed)
    frame_devices, slices = fleets[0].frame_devices, fleets[0].slices
    obs: dict = {"tenants": n_tenants, "devices": devices, "fleets": fleets,
                 "frame_devices": frame_devices, "slices": slices,
                 "first_tick": hist_ticks}

    memory = MemoryWatch()

    def lap(what: str) -> None:
        memory.sample()
        log(f"set-up {time.monotonic() - t_process:7.2f}s  {what}")

    lap("jax up, devices checked")
    rt = await start_runtime(f"bench-{cell.name}")
    lap("runtime started")
    feeder = collector = None
    try:
        im = rt.services["instance-management"]
        sections = tenant_sections(cfg)
        history = []
        for tid, fleet in zip(tenant_ids, fleets):
            await im.create_tenant(tid, tid, sections)
            history.append(seed_history(rt, tid, fleet, hist_ticks))
        obs["history"] = history
        lap("tenants created, fleets registered, history seeded")
        engines = [rt.api("rule-processing").engine(t) for t in tenant_ids]
        pooled = engines[0].session is None
        sink = engines[0].pool_slot.pool if pooled else engines[0].session
        if not pooled:
            await wait_warm(sink, "scoring warm-up")
        # weights from the seed, one tenant one set, installed as a
        # checkpoint roll-out; the swap re-seeds the device state from
        # the history under the new weights
        for i, engine in enumerate(engines):
            engine.swap_model_params(
                cell.model.tenant_params(seed, i, cfg["model_config"]))
        await wait_warm(sink, "scoring warm-up")
        jax.block_until_ready(sink.ring.state)
        lap("weights installed, state seeded, every shape warm")
        mesh = getattr(sink, "mesh", None)
        obs["mesh"] = dict(mesh.shape) if mesh is not None else None
        if cfg.get("mesh") and obs["mesh"] != dict(cfg["mesh"]):
            raise RuntimeError(f"configuration asks for mesh {cfg['mesh']}, "
                               f"the pool built {obs['mesh']}")

        es = rt.api("event-sources")
        collector = Collector(rt, tenant_ids, frame_devices)
        feeder = await Feeder.start({
            "seed": seed, "first_frame": hist_ticks * slices,
            "anomaly_rate": cfg["anomaly_rate"],
            "anomaly_magnitude": cfg["anomaly_magnitude"],
            "loop": traffic["loop"],
            "frames_per_s": traffic.get("frames_per_s"),
            "inflight_frames": traffic.get("inflight_frames"),
            "ahead": traffic.get("encode_ahead", 32),
            "targets": [{"tenant": i, "devices": devices,
                         "frame_devices": frame_devices,
                         "port": es.engine(t).receiver(GATEWAY).port}
                        for i, t in enumerate(tenant_ids)]})
        # warm-up beats through the whole served path, one at a time; one
        # that does not come out whole is the comparison's to report
        for k in range(warm_beats):
            feeder.tell("T")
            await feeder.line(30.0)
            t0 = time.monotonic()
            while collector.beats_done() < k + 1:
                if time.monotonic() - t0 > WARM_BEAT_S:
                    log(f"warm-up beat {k} not whole after {WARM_BEAT_S}s")
                    break
                await asyncio.sleep(0.002)
        lap(f"feeder up, {warm_beats} warm-up beats served")
        before = snapshot_metrics(rt)
        compiles_before = compiles.compiles
        if traffic["loop"] == "closed":
            collector.start_credits(feeder)
        start = time.monotonic() + 0.05
        end = start + seconds
        obs["setup_s"] = start - t_process    # process start to window open
        feeder.tell(f"W {start!r} {seconds!r}")
        log(f"window opens: set-up took {obs['setup_s']:.2f}s")
        tracing = None
        if trace:
            length = min(TRACE_MAX_S, seconds * TRACE_SHARE)
            obs["trace_dir"] = os.path.join(cell.root, ".bench_trace",
                                            cell.name)
            shutil.rmtree(obs["trace_dir"], ignore_errors=True)
            tracing = asyncio.create_task(traced_slice(
                obs["trace_dir"], start + seconds * TRACE_START, length))
        await asyncio.sleep(max(end - time.monotonic(), 0.0))
        after = snapshot_metrics(rt)
        memory.sample()
        obs["compiles_in_window"] = compiles.compiles - compiles_before
        report = json.loads(await feeder.line(60.0))
        if tracing is not None:
            obs["trace_slice"] = await tracing
        # drain: wait for every frame sent, a minute past the close if
        # need be; a rejected frame (counted by the program) never comes
        sent_beats = warm_beats + len(report["due"])
        want = sent_beats * frame_devices * n_tenants
        rejected0 = before["counters"].get("flow.rejected", 0.0)
        t_moved, last = time.monotonic(), -1
        while True:
            got = collector.total()
            rejected = rt.metrics.counter("flow.rejected").value - rejected0
            now = time.monotonic()
            if got != last:
                last, t_moved = got, now
            if got + rejected >= want or now - end > DRAIN_DEADLINE_S \
                    or now - t_moved > DRAIN_STALL_S:
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.25)       # anything later would be a duplicate
        obs["drain_s"] = time.monotonic() - end
        final = snapshot_metrics(rt)
        memory.sample()
        obs.update(
            start=start, end=end, report=report, warm_beats=warm_beats,
            window_metrics=metrics_delta(before, after),
            run_counters=final["counters"],
            rejected_events=final["counters"].get("flow.rejected", 0.0)
            - rejected0,
            alerts_stored=sum(len(rt.api("event-management").management(t)
                                  .alerts) for t in tenant_ids),
            memory=memory.report())
        await collector.close()
        obs["records"] = collector.records
    finally:
        if feeder is not None:
            await feeder.stop()
        if collector is not None and not collector._task.done():
            await collector.close()
        await asyncio.wait_for(rt.stop(), 60.0)
    return obs


def reduce_records(obs: dict) -> dict:
    """From what was seen on `scored-events` to per-frame completion
    times, served scores by (tick, device) and the delivery counts.
    Beat `b` of the run (warm-up beats first) carried, for every tenant,
    slice `b % slices` of tick `b // slices` (gen.py)."""
    n_tenants, devices = obs["tenants"], obs["devices"]
    fd, slices, first = obs["frame_devices"], obs["slices"], obs["first_tick"]
    n_beats = obs["warm_beats"] + len(obs["report"]["due"])
    n_ticks = -(-n_beats // slices)
    served = np.full((n_tenants, n_ticks, devices), np.nan, np.float32)
    flagged = np.zeros((n_tenants, n_ticks, devices), bool)
    copies = np.zeros((n_tenants, n_ticks, devices), np.uint8)
    done = np.full((n_tenants, n_ticks * slices), np.nan)
    last_tick = np.full((n_tenants, devices), -1, np.int64)
    reordered = stray = in_window = 0
    for seen, tenant, scored in obs["records"]:
        all_ticks = gen.tick_of(scored.ts) - first
        all_dev = np.asarray(scored.device_index, np.int64)
        all_score = np.asarray(scored.score, np.float32)
        all_flag = np.asarray(scored.is_anomaly, bool)
        ok = (all_ticks >= 0) & (all_ticks < n_ticks) & (all_dev < devices)
        stray += int((~ok).sum())
        for k in np.unique(all_ticks[ok]):
            pick = ok & (all_ticks == k)
            dev = all_dev[pick]
            # within one device, events come out in the order they went in
            reordered += int((k < last_tick[tenant, dev]).sum())
            last_tick[tenant, dev] = np.maximum(last_tick[tenant, dev], k)
            served[tenant, k, dev] = all_score[pick]
            flagged[tenant, k, dev] = all_flag[pick]
            lo, hi = int(dev.min()), int(dev.max()) + 1
            copies[tenant, k, lo:hi] += np.bincount(
                dev - lo, minlength=hi - lo).astype(np.uint8)
            beats = k * slices + np.unique(dev // fd)
            done[tenant, beats] = np.fmax(done[tenant, beats], seen)
        if obs["start"] <= seen < obs["end"]:
            in_window += len(scored)
    # the beat each (tick, device) event went out in: the last tick may
    # have been cut short by the window's end
    beat_of = np.repeat(np.arange(n_ticks * slices, dtype=np.int32)
                        .reshape(n_ticks, slices), fd, axis=1)
    return {"served": served, "flagged": flagged, "copies": copies,
            "sent": beat_of < n_beats,
            "in_window": (beat_of >= obs["warm_beats"]) & (beat_of < n_beats),
            "done": done[:, :n_beats], "reordered": reordered,
            "stray": stray, "events_in_window": in_window}


def end_to_end(obs: dict, red: dict, seconds: float) -> dict:
    """The client's numbers. A frame's latency runs from the instant it
    was due to the instant this process saw the last of its events on
    `scored-events`; a frame that never came has waited until the drain
    closed, and counts as missing any limit."""
    due = np.asarray(obs["report"]["due"], np.float64)
    done = red["done"][:, obs["warm_beats"]:]            # [tenants, beats]
    gave_up = obs["end"] + obs["drain_s"]
    latency = (np.where(np.isnan(done), gave_up, done) - due[None, :]) * 1e3
    latency = latency.ravel()
    out = {"events_per_s": red["events_in_window"] / seconds,
           "setup_s": obs["setup_s"]}
    if latency.size:
        out["latency_p50_ms"] = float(np.percentile(latency, 50))
        out["latency_p95_ms"] = float(np.percentile(latency, 95))
    obs["latency_ms"] = latency
    return out


def decide_correct(cell: Cell, obs: dict, red: dict, seed: int) -> tuple:
    """The comparison with the plain reference: every served score of
    every frame, warm-up beats included, plus the delivery the
    configuration states. Frames the program refused at ingress (it
    counts them) are not fed to the reference either."""
    cfg = cell.config
    first, fd = obs["first_tick"], obs["frame_devices"]
    copies, served, sent = red["copies"], red["served"], red["sent"]
    missing = int(((copies == 0) & sent[None]).sum())
    lost = max(missing - int(round(obs["rejected_events"])), 0)
    gap_max = gap_sum = n_cmp = mismatches = 0
    for i, fleet in enumerate(obs["fleets"]):
        have = copies[i] > 0
        if not have.any():
            continue
        # a frame is fed to the reference when any of it was served
        n_ticks = have.shape[0]
        fed = np.repeat(have.reshape(n_ticks, -1, fd).any(axis=2), fd, axis=1)
        frames = np.stack([fleet.values(first + k) for k in range(n_ticks)])
        params = cell.model.tenant_params(seed, i, cfg["model_config"])
        ref = cell.model.run(params, obs["history"][i], frames, fed,
                             cfg["model_config"], cfg["compute_dtype"])
        g_max, g_mean = compare.score_gaps(served[i][have], ref[have])
        gap_max = max(gap_max, g_max)
        gap_sum += g_mean * int(have.sum())
        n_cmp += int(have.sum())
        mismatches += compare.alert_mismatches(
            red["flagged"][i][have], ref[have], cfg["threshold"],
            cfg["limits"]["score_gap_max"])
    published_alerts = int(red["flagged"].sum())
    emitted = obs["run_counters"].get("rules.alerts_emitted", 0.0)
    numbers = {
        "score_gap_max": gap_max if n_cmp else float("inf"),
        "score_gap_mean": gap_sum / n_cmp if n_cmp else float("inf"),
        "alert_mismatches": mismatches,
        "lost_events": lost,
        "duplicate_events": int((copies > 1).sum()) + red["stray"],
        "reordered_events": red["reordered"],
        "alerts_not_emitted": abs(published_alerts - int(round(emitted))),
        "alerts_not_stored": int(published_alerts > 0
                                 and obs["alerts_stored"] == 0),
        "failed_health": int(sum(obs["run_counters"].get(n, 0.0) for n in (
            "supervisor.restarts", "dlq.quarantined",
            "egress.publish_failures"))),
        "compiles_in_window": obs["compiles_in_window"],
    }
    ok, checks = compare.verdict(numbers, cfg["limits"])
    return ok, checks, {"compared_events": n_cmp, "missing_events": missing}


def per_layer(cell: Cell, obs: dict) -> dict:
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        spec = load_json(cell.data_dir, "metrics", f"{m['name']}.json")
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None and np.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             expect_platform: str, root: str = ROOT,
             t_process: float | None = None,
             traffic_override: dict | None = None) -> tuple[dict, dict]:
    """Run one cell; returns the result (what `main` prints as the last
    line) and what else was worth knowing (which it prints before it).
    Raises SystemExit(2), printing no result, when JAX's devices are not
    `expect_platform` or are fewer than the cell asks for.
    `traffic_override` is for benchmarks/sweep.py alone."""
    t_process = time.monotonic() if t_process is None else t_process
    cell = Cell(root, workload)
    cell.traffic.update(traffic_override or {})
    import jax

    from sitewhere_tpu.utils.backend import device_summary, use_compile_cache

    cache_dir = use_compile_cache()
    # keep every program, however quick its compile: set-up then costs
    # the same from the second run on (JAX's default keeps only those
    # that took over a second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t_backend = time.monotonic()
    platform, kind, count = device_summary()
    starts = {"imports_s": t_backend - t_process,
              "backend_init_s": time.monotonic() - t_backend}
    log(f"cell={workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"platform={platform} kind={kind!r} count={count} cache={cache_dir}")
    if platform != expect_platform or count < cell.chips:
        log(f"need {cell.chips} x {expect_platform!r}, JAX has "
            f"{count} x {platform!r}: not running")
        raise SystemExit(2)
    peaks = None
    if platform == "tpu":
        table = load_json(HERE, "peaks.json")
        if kind not in table:
            log(f"device_kind {kind!r} is not in benchmarks/peaks.json")
            raise SystemExit(2)
        peaks = table[kind]

    compiles = CompileCounter()
    try:
        obs = asyncio.run(serve_and_measure(cell, seed, seconds, trace,
                                            t_process, compiles))
    finally:
        compiles.close()
    red = reduce_records(obs)
    metrics = end_to_end(obs, red, seconds)
    sent = len(obs["report"]["due"]) * obs["frame_devices"] * obs["tenants"]
    done_events = int(((red["copies"] > 0) & red["in_window"][None]).sum())
    device = {"platform": platform, "kind": kind, "count": count,
              **obs["memory"]}
    result = {"correct": False, "attempted": sent,
              "failed": sent - done_events, "metrics": {}, "device": device}
    if trace:
        mc = cell.config["model_config"]
        obs.update(cell=cell.name, config=cell.config, peaks=peaks,
                   seconds=seconds, chips=cell.chips,
                   events_in_window=red["events_in_window"],
                   flops_per_event=cell.model.flops_per_event(mc),
                   bytes_per_event=cell.model.bytes_per_event(
                       mc, cell.config["score_dtype"]))
        obs["trace"] = xplane.reduce_run(obs)
        shutil.rmtree(obs["trace_dir"], ignore_errors=True)
        result["metrics"] = per_layer(cell, obs)
        if obs["trace"]:
            device["busy_s"] = obs["trace"]["busy_s"]
            device["window_s"] = obs["trace"]["window_s"]
            result["breakdown"] = obs["trace"]["breakdown"]
    else:
        units = {m["name"]: m["unit"] for m in cell.metrics("end_to_end")}
        result["metrics"] = {n: {"value": float(metrics[n]), "unit": u}
                             for n, u in units.items()}
    # the reference runs last: the window has closed, the peak has been
    # read and the program's state is gone
    ok, checks, extra = decide_correct(cell, obs, red, seed)
    result["correct"] = ok
    info = {"end_to_end": metrics, "drain_s": obs["drain_s"],
            "frames": len(obs["report"]["due"]),
            "rejected_events": obs["rejected_events"], "mesh": obs["mesh"],
            "starts": starts, **extra}
    if trace and obs["trace"]:
        # seconds on the device by jitted program, as the trace names them
        info["trace_modules_s"] = obs["trace"]["modules"]
        info["trace_steps"] = obs["trace"]["steps"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})")
    log(f"correct={ok}")
    return result, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, info = run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), "tpu", t_process=_T_PROCESS)
    print(json.dumps({"info": info}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
