"""chip_smoke.py — the quickest proof that swx still starts on the chip.

Drives the main path once, through the entry points a user would call,
at the full width of the model the repo serves (LSTM window 64, hidden
64; weights random from a seed), and checks what comes out by the
repo's own means:

  A  the server answers requests: the `swx run` runtime (every
     service), one `lstm-stream` tenant, a TCP SWB1 gateway fed by ONE
     child process that never touches JAX; scored == sent == published,
     alerts emitted, state on the expected devices, no restarts / dead
     letters / publish failures, no compile after warm-up; the compiled
     ring step moves no whole table (no `copy` / `transpose` of a leaf
     of two or more dimensions), and the summary says how each state
     leaf rests on the device.
  B  the Pallas kernel (ops/lstm_kernel.py): a dedicated windowed-`lstm`
     session must select it, compile it under Mosaic (not interpret
     mode) and agree with the `lax.scan` scorer on the same windows.
  C  the shared pool: tenants on `lstm-stream` through the megabatch
     pool, one param hot-swap under load; with four or more devices the
     pool must shard over exactly `{data: 2, model: 2}`.
  D  `dsv3-stream` (DeepSeek-V3's block at the published widths, the
     benchmark configuration's share of it) behind the same gateway at a
     small fleet: the session holds no weights until the first set is
     bound, then scored == sent == published; the compiled step copies
     or transposes no context leaf; the arrays' peak stayed under one
     set of weights and a half, so two sets were never resident.
  E  `laguna-stream` (Laguna-S-2.1's block at the published widths, the
     benchmark configuration's share of it) the same way: a windowed
     and a full key-value context side by side in a device's ring row,
     the table as long as the fleet and no longer; scored == sent ==
     published; the compiled step copies or transposes no context leaf.

This process is the one that holds the chip. It takes no flags, reads
no switch of its own and never sets JAX_PLATFORMS: `python chip_smoke.py`
exits non-zero — printing no result — unless JAX's first device is a
TPU. Progress goes to stderr; stdout carries one JSON line of counts and
per-phase results, then, as its last line, the verdict
`{"ok": ..., "device": {"platform", "kind", "count"}}`. It prints no
rate and nothing under a benchmark metric's name.

`run_smoke` is the body: tests/test_chip_smoke.py runs the same control
flow tiny on the CPU.
"""

from __future__ import annotations

import asyncio
import json
import os
import re
import sys
import time
import traceback
from dataclasses import dataclass

import jax
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

WINDOW, HIDDEN = 64, 64      # the repo's full width (BASELINE.json config 2)
# alert bar and injected spike, as benchmarks/configs/stream-512k.json
THRESHOLD = 6.0
ANOMALY_MAGNITUDE = 12.0
PARITY_ATOL = 3e-2           # kernel vs scan, as tests/test_pallas.py
GATEWAY = "gw"               # the tenants' TCP receiver
# the stores are seeded with WINDOW+4 clean ticks a minute apart; the
# feeder's stream continues from there
SEED_TICKS = WINDOW + 4
FEED_T0 = 60.0 * SEED_TICKS


@dataclass(frozen=True)
class Sizes:
    """How big a run is. `FULL` is the chip check; tests run it tiny."""
    devices: int            # phase A fleet = bucket = ring capacity
    ticks: int              # ticks the feeder sends, one event a device
    pool_tenants: int       # phase C
    pool_devices: int
    kernel_bucket: int      # phase B
    anomaly_rate: float     # share of events the feeder spikes
    interval_s: float       # feeder's tick period
    dsv3_devices: int       # phase D fleet = bucket = ring capacity
    # phase D `model_config`; None: the one the benchmark's DeepSeek-V3
    # configuration runs (benchmarks/configs/deepseek-v3-ep16.json)
    dsv3_config: dict | None = None
    laguna_devices: int = 256     # phase E fleet = bucket = ring capacity
    # phase E `model_config`; None: benchmarks/configs/laguna-s-2.1-ep8.json's
    laguna_config: dict | None = None


FULL = Sizes(devices=16384, ticks=32, pool_tenants=8, pool_devices=2048,
             kernel_bucket=4096, anomaly_rate=0.001, interval_s=0.125,
             dsv3_devices=1024)

# the whole run must fit the chip check's 1200 s, compilation included
WARM_DEADLINE_S = 420.0
FEED_DEADLINE_S = 180.0
RUN_DEADLINE_S = 1100.0

# The load generator: a separate process that imports numpy and the
# simulator only (no JAX backend — the chip belongs to the parent),
# encodes each tick as SWB1 and writes u32-LE length-prefixed frames to
# the tenants' TCP gateways, one tick per `interval_s`.
_FEEDER_SRC = r'''
import json, socket, struct, sys, time
cfg = json.loads(sys.argv[1])
sys.path.insert(0, cfg["repo"])
from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

lanes = []
for t in cfg["targets"]:
    sim = DeviceSimulator(SimConfig(num_devices=cfg["devices"],
                                    anomaly_rate=cfg["anomaly_rate"],
                                    anomaly_magnitude=cfg["magnitude"]),
                          tenant_id=t["tenant"])
    lanes.append((sim, socket.create_connection(("127.0.0.1", t["port"]))))
sent = 0
next_t = time.monotonic()
for k in range(cfg["ticks"]):
    for sim, sock in lanes:
        payload, _ = sim.payload(t=cfg["t0"] + 60.0 * k)
        sock.sendall(struct.pack("<I", len(payload)) + payload)
        sent += cfg["devices"]
    next_t += cfg["interval_s"]
    time.sleep(max(next_t - time.monotonic(), 0.0))
for _, sock in lanes:
    sock.close()
print("SENT", sent, flush=True)
'''


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA executables this process builds or loads (JAX's
    backend-compile event fires for persistent-cache hits too) and the
    persistent-cache hits among them. JAX has no public unregister, so
    `close()` just makes the listeners inert."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self._live = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if self._live and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if self._live and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        self._live = False


class Phase:
    """One phase's record: counts, timings and every failed check."""

    def __init__(self, name: str, counter: CompileCounter):
        self.name = name
        self.out: dict = {}
        self.fails: list[str] = []
        self._counter = counter
        self._c0 = (counter.compiles, counter.cache_hits)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.fails.append(what)
            log(f"phase {self.name}: FAILED check: {what}")

    def compiles(self) -> int:
        """Executables built or loaded so far (subtract two marks)."""
        return self._counter.compiles

    def result(self) -> dict:
        self.out.update(compiles=self._counter.compiles - self._c0[0],
                        cache_hits=self._counter.cache_hits - self._c0[1],
                        ok=not self.fails)
        if self.fails:
            self.out["failed"] = self.fails
        return self.out


async def _wait_warm(sink, what: str) -> None:
    """Wait for a session's or pool's warm-up. One that cannot succeed
    retries for ever, so on the deadline say what it keeps failing on
    (the compiler's message), not just that time ran out."""
    t0 = time.monotonic()
    while not sink.ready:
        if time.monotonic() - t0 > WARM_DEADLINE_S:
            raise TimeoutError(
                f"{what} not done in {WARM_DEADLINE_S:.0f}s; last "
                f"warm-up error: {sink.warmup_error!r}")
        await asyncio.sleep(0.05)


async def _wait_stream(progress, want: int, proc) -> None:
    """Wait until `progress()` reaches `want`. Gives up early — a lost
    frame never arrives — once the feeder has exited and nothing has
    moved for 10 s; `FEED_DEADLINE_S` is the hard bound."""
    t0 = t_moved = time.monotonic()
    last = -1
    while (n := progress()) < want:
        now = time.monotonic()
        if n != last:
            last, t_moved = n, now
        if now - t0 > FEED_DEADLINE_S or (
                proc.returncode is not None and now - t_moved > 10.0):
            raise TimeoutError(f"stream stalled at {n} of {want} events")
        await asyncio.sleep(0.05)


def _shard_devices(tree) -> set:
    """Devices that hold an addressable shard of any leaf of `tree`."""
    return {shard.device for leaf in jax.tree.leaves(tree)
            for shard in leaf.addressable_shards}


_HLO_OP = re.compile(r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]\S* (\w[\w-]*)\(")


def _table_moves(hlo: str, rows: int) -> list[str]:
    """`copy` / `transpose` operations of a compiled step whose output is
    a table of `rows` rows and two or more dimensions, whichever axis
    the rows lie on: a leaf that rests in another layout than the
    scatter wants shows up here, twice a step (PERF.md, PR 27)."""
    moves = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m and m.group(3) in ("copy", "transpose"):
            dims = m.group(2).split(",")
            if len(dims) >= 2 and str(rows) in dims:
                moves.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    return moves


def _ring_step_checks(ph: Phase, session, enforce: bool = True) -> None:
    """Compile the dedicated ring's warmed step once more, for its text
    (a compile before the warm-up mark), and read the state's layouts.
    `enforce` False records the moves without failing on them."""
    ring = session.ring
    bucket = session.cfg.buckets[-1]
    dev, v = ring._pad(np.zeros(0, np.int32), np.zeros(0, np.float32),
                       bucket)
    hlo = ring._fns[ring.capacity, bucket].lower(
        session.params, ring.state, dev, v).compile().as_text()
    moves = _table_moves(hlo, ring.capacity + 1)
    ph.out["table_moves"] = moves
    ph.check(not (moves and enforce),
             f"the ring step moves no whole table (got {moves})")
    ph.out["state_layouts"] = {
        name: f"{list(leaf.shape)} major_to_minor="
              f"{leaf.format.layout.major_to_minor} "
              f"tiling={leaf.format.layout.tiling}"
        for name, leaf in sorted(ring.state.items())}


def _tenant_sections(devices: int, model: str = "lstm-stream",
                     model_config: dict | None = None, **rule_extra) -> dict:
    return {
        "rule-processing": {
            "model": model,
            "model_config": model_config or {"window": WINDOW,
                                             "hidden": HIDDEN},
            "threshold": THRESHOLD,
            # bucket = ring capacity = fleet, as
            # benchmarks/configs/stream-512k.json sizes them
            "buckets": [devices], "capacity": devices,
            **rule_extra,
        },
        "event-sources": {"receivers": [
            {"kind": "tcp", "decoder": "swb1", "name": GATEWAY,
             "port": 0}]},
    }


async def _start_runtime(instance_id: str):
    """The runtime `swx run` builds: every service, REST on an ephemeral
    port, default settings."""
    from sitewhere_tpu.cli import _build_runtime
    from sitewhere_tpu.config import InstanceSettings

    rt = _build_runtime(InstanceSettings(instance_id=instance_id,
                                         rest_port=0), [])
    await rt.start()
    return rt


def _seed_history(rt, tenant_id: str, devices: int) -> None:
    """Register the fleet and put SEED_TICKS clean ticks straight into
    the host store (set-up, not traffic), so the scorer's short-history
    gate is open from the first event."""
    from sitewhere_tpu.domain.model import DeviceType
    from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

    rt.api("device-management").management(tenant_id).bootstrap_fleet(
        DeviceType(token="thermo", name="Thermometer"), devices)
    em = rt.api("event-management").management(tenant_id)
    sim = DeviceSimulator(SimConfig(num_devices=devices),
                          tenant_id=tenant_id)
    for k in range(SEED_TICKS):
        em.telemetry.append_measurements(sim.tick(t=60.0 * k)[0])


async def _feed(rt, tenant_ids: list, devices: int, sizes: Sizes):
    """Start the load-generator child against the tenants' gateways;
    returns the process handle."""
    es = rt.api("event-sources")
    cfg = {"repo": REPO, "devices": devices, "ticks": sizes.ticks,
           "t0": FEED_T0, "anomaly_rate": sizes.anomaly_rate,
           "magnitude": ANOMALY_MAGNITUDE, "interval_s": sizes.interval_s,
           "targets": [
               {"tenant": tid,
                "port": es.engine(tid).receiver(GATEWAY).port}
               for tid in tenant_ids]}
    # JAX_PLATFORMS=cpu in the CHILD's environment only: even an
    # accidental jax import there could not reach for the chip
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return await asyncio.create_subprocess_exec(
        sys.executable, "-c", _FEEDER_SRC, json.dumps(cfg),
        stdout=asyncio.subprocess.PIPE, env=env)


async def _reap(proc) -> tuple[int, int]:
    """Wait for the feeder; returns (exit code, events it says it sent)."""
    out, _ = await asyncio.wait_for(proc.communicate(), FEED_DEADLINE_S)
    sent = 0
    for line in out.decode().splitlines():
        if line.startswith("SENT "):
            sent = int(line.split()[1])
    return proc.returncode, sent


async def _stop_feeder(proc) -> None:
    """Every process this script starts is stopped before it returns."""
    if proc is not None and proc.returncode is None:
        proc.kill()
        await proc.wait()


def _drain_topic(consumer, seen: dict) -> None:
    for record in consumer.poll_nowait(max_records=512):
        scored = record.value
        seen["events"] += len(scored)
        seen["versions"].add(int(scored.model_version))


def _health_checks(ph: Phase, rt) -> None:
    for name in ("supervisor.restarts", "dlq.quarantined",
                 "egress.publish_failures", "flow.rejected"):
        value = int(rt.metrics.counter(name).value)
        ph.out[name] = value
        ph.check(value == 0, f"{name} == 0 (got {value})")


# -- phase A: the server answers requests ------------------------------------

async def phase_server(ph: Phase, expect_platform: str,
                       sizes: Sizes) -> None:
    tid, devices = "smoke", sizes.devices
    rt = await _start_runtime("chip-smoke-a")
    proc = None
    try:
        t_warm = time.monotonic()
        im = rt.services["instance-management"]
        await im.create_tenant(tid, "Smoke", _tenant_sections(devices))
        _seed_history(rt, tid, devices)
        engine = rt.api("rule-processing").engine(tid)
        session = engine.session
        ph.check(session is not None and engine.fastlane is not None
                 and engine.egress is not None,
                 "dedicated session, fast lane and fused egress engaged "
                 "by default")
        await _wait_warm(session, "scoring warm-up")
        session.reload_history()
        jax.block_until_ready(session.ring.state)
        ph.out["warmup_s"] = round(time.monotonic() - t_warm, 2)
        log(f"phase A: warm in {ph.out['warmup_s']}s")

        platforms = {d.platform for d in _shard_devices(session.ring.state)}
        ph.out["state_platforms"] = sorted(platforms)
        ph.check(platforms == {expect_platform},
                 f"ring state on {expect_platform} devices "
                 f"(got {sorted(platforms)})")
        _ring_step_checks(ph, session)

        consumer = rt.bus.subscribe(
            rt.naming.tenant_topic(tid, "scored-events"), group="chip-smoke")
        seen = {"events": 0, "versions": set()}
        scored0 = session.flights.latency.count
        warm_mark = ph.compiles()
        proc = await _feed(rt, [tid], devices, sizes)
        want = devices * sizes.ticks

        def progress() -> int:
            _drain_topic(consumer, seen)
            return min(session.flights.latency.count - scored0, seen["events"])

        try:
            await _wait_stream(progress, want, proc)
        except TimeoutError as exc:
            ph.check(False, str(exc))
        rc, sent = await _reap(proc)
        await asyncio.sleep(0.25)       # anything late would be a duplicate
        _drain_topic(consumer, seen)
        consumer.close()
        scored = session.flights.latency.count - scored0
        alerts = len(rt.api("event-management").management(tid).alerts)
        ph.out.update(sent=sent, scored=scored, published=seen["events"],
                      alerts=alerts, feeder_exit=rc,
                      compiles_after_warmup=ph.compiles() - warm_mark)
        ph.check(rc == 0, f"feeder exited 0 (got {rc})")
        ph.check(sent == want, f"feeder sent {want} (said {sent})")
        ph.check(scored == sent == seen["events"],
                 f"scored == sent == published "
                 f"({scored} / {sent} / {seen['events']})")
        ph.check(alerts >= 1, "at least one alert reached the event store")
        ph.check(ph.out["compiles_after_warmup"] == 0,
                 f"no compile after warm-up "
                 f"(got {ph.out['compiles_after_warmup']})")
        _health_checks(ph, rt)
    finally:
        await _stop_feeder(proc)
        await asyncio.wait_for(rt.stop(), 60.0)
    log(f"phase A: {ph.out}")


# -- phase B: the kernel compiles --------------------------------------------

async def phase_kernel(ph: Phase, expect_platform: str,
                       sizes: Sizes) -> None:
    from sitewhere_tpu.kernel.metrics import MetricsRegistry
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.ops.lstm_kernel import pallas_ok
    from sitewhere_tpu.persistence.telemetry import TelemetryStore
    from sitewhere_tpu.scoring.server import ScoringConfig, ScoringSession
    from sitewhere_tpu.sim.simulator import DeviceSimulator, SimConfig

    bucket = sizes.kernel_bucket
    model = build_model("lstm", window=WINDOW, hidden=HIDDEN)
    if not pallas_ok(bucket, model.cfg.layers, model.cfg.compute_dtype):
        # off-chip the predicate declines and the scan is the only path;
        # on the chip a kernel that is present must be selected
        ph.out["skipped"] = f"pallas_ok false on {expect_platform}"
        ph.check(expect_platform != "tpu",
                 "the kernel is selected on a TPU at this bucket")
        log(f"phase B: not run ({ph.out['skipped']})")
        return
    store = TelemetryStore(history=2 * WINDOW, initial_devices=bucket)
    sim = DeviceSimulator(SimConfig(num_devices=bucket), tenant_id="kernel")
    for k in range(SEED_TICKS):
        store.append_measurements(sim.tick(t=60.0 * k)[0])
    # float32 readback: the parity check compares scores, not their
    # float16 transport
    session = ScoringSession(
        model, store, MetricsRegistry(),
        ScoringConfig(buckets=(bucket,), capacity=bucket,
                      threshold=THRESHOLD, score_dtype="float32"))
    try:
        t_warm = time.monotonic()
        session.warmup()        # a refused Mosaic compile raises here
        ph.out["warmup_s"] = round(time.monotonic() - t_warm, 2)
        ring = session.ring
        ph.out["fused_status"] = ring.fused_status
        ph.check(ring.fused_status == "compiled",
                 f"fused_status == 'compiled' (got {ring.fused_status!r})")
        step = ring._update_score_fns.get((ring.capacity, bucket))
        mosaic = step is not None and "tpu_custom_call" in step.as_text()
        ph.out["mosaic_custom_call"] = mosaic
        ph.check(mosaic, "the compiled step holds a Mosaic custom call "
                         "(not interpret mode)")

        batch, _ = sim.tick(t=FEED_T0)
        session.admit(batch)
        scored = await asyncio.wait_for(session.flush(), 120.0)
        dev = np.asarray(scored.device_index, np.int32)
        x, valid = ring.windows(dev)          # the windows just scored
        want = np.asarray(jax.jit(model.score)(session.params, x, valid))
        got = np.asarray(scored.score, np.float32)
        err = float(np.abs(got - want).max())
        ph.out.update(scored=int(got.shape[0]),
                      max_abs_err_vs_scan=round(err, 6))
        ph.check(got.shape == (bucket,) and bool(np.isfinite(got).all()),
                 f"{bucket} finite scores")
        ph.check(bool((want > 0).any()), "reference scores are not all 0")
        ph.check(err <= PARITY_ATOL,
                 f"kernel scores agree with model.score within "
                 f"{PARITY_ATOL} (max abs err {err:.4g})")
    finally:
        session.close()
    log(f"phase B: {ph.out}")


# -- phase C: the pool -------------------------------------------------------

async def phase_pool(ph: Phase, expect_platform: str, n_devices: int,
                     sizes: Sizes) -> None:
    tenants, devices = sizes.pool_tenants, sizes.pool_devices
    mesh_spec = {"data": 2, "model": 2} if n_devices >= 4 else None
    rule_extra = {"megabatch": {"enabled": True}}
    if mesh_spec:
        rule_extra["mesh"] = dict(mesh_spec)
    tids = [f"pool{i}" for i in range(tenants)]
    rt = await _start_runtime("chip-smoke-c")
    proc = None
    try:
        t_warm = time.monotonic()
        im = rt.services["instance-management"]
        for tid in tids:
            await im.create_tenant(tid, tid, _tenant_sections(
                devices, **rule_extra))
            _seed_history(rt, tid, devices)
        engines = {tid: rt.api("rule-processing").engine(tid) for tid in tids}
        slots = {tid: e.pool_slot for tid, e in engines.items()}
        ph.check(all(s is not None for s in slots.values())
                 and len({id(s.pool) for s in slots.values()}) == 1,
                 "every tenant rides ONE shared pool")
        pool = slots[tids[0]].pool
        # reseed first: the pool sized its ring before the fleets existed,
        # so this grows it and re-warms at the shapes the traffic will hit
        for slot in slots.values():
            slot.reload_history()
        await _wait_warm(pool, "pool warm-up")
        jax.block_until_ready(pool.ring.state)
        ph.out["warmup_s"] = round(time.monotonic() - t_warm, 2)
        log(f"phase C: warm in {ph.out['warmup_s']}s")

        if mesh_spec is None:
            ph.out["mesh"] = "not run (1 chip)"
            log(f"mesh: not run ({n_devices} chip)")
            ph.check(pool.mesh is None, "meshless pool on one device")
        else:
            shape = dict(pool.mesh.shape) if pool.mesh is not None else None
            ph.out["mesh"] = shape
            ph.check(shape == mesh_spec,
                     f"pool mesh is exactly {mesh_spec} (got {shape})")
            for what, tree in (("ring state", pool.ring.state),
                               ("stacked params", pool.stack.stacked)):
                n_held = len(_shard_devices(tree))
                ph.check(n_held == 4, f"{what} has shards on 4 distinct "
                                      f"devices (got {n_held})")
            if expect_platform == "tpu":
                in_use = [d.memory_stats()["bytes_in_use"]
                          for d in pool.mesh.devices.flat]
                ph.out["bytes_in_use"] = in_use
                ph.check(all(b > 0 for b in in_use),
                         f"non-zero memory on all four devices ({in_use})")
        platforms = {d.platform for d in _shard_devices(pool.ring.state)}
        ph.check(platforms == {expect_platform},
                 f"pool state on {expect_platform} devices "
                 f"(got {sorted(platforms)})")

        consumers = {tid: rt.bus.subscribe(
            rt.naming.tenant_topic(tid, "scored-events"),
            group="chip-smoke") for tid in tids}
        seen = {tid: {"events": 0, "versions": set()} for tid in tids}
        new_params = pool.model.init(jax.random.PRNGKey(21))
        warm_mark = ph.compiles()
        proc = await _feed(rt, tids, devices, sizes)
        want = devices * sizes.ticks

        def drain() -> int:
            for tid in tids:
                _drain_topic(consumers[tid], seen[tid])
            return min(s["events"] for s in seen.values())

        # one hot-swap UNDER LOAD: as soon as every tenant's first tick
        # is out, swap tenant 0's weights; that it landed mid-stream is
        # checked below (tenant 0 publishes under BOTH versions)
        await _wait_stream(drain, devices, proc)
        version = engines[tids[0]].swap_model_params(new_params)
        try:
            await _wait_stream(drain, want, proc)
        except TimeoutError as exc:
            ph.check(False, str(exc))
        rc, sent = await _reap(proc)
        await asyncio.sleep(0.25)
        drain()
        for c in consumers.values():
            c.close()

        versions = {tid: slots[tid].version for tid in tids}
        per_tenant = {tid: seen[tid]["events"] for tid in tids}
        dispatches = int(rt.metrics.counter(
            "scoring.megabatch_dispatches").value)
        ph.out.update(
            sent_per_tenant=want, feeder_sent=sent, feeder_exit=rc,
            scored_per_tenant=sorted(set(per_tenant.values())),
            megabatch_dispatches=dispatches, swap_version=version,
            compiles_after_warmup=ph.compiles() - warm_mark)
        ph.check(rc == 0 and sent == want * tenants,
                 f"feeder exited 0 having sent {want * tenants} "
                 f"(rc {rc}, said {sent})")
        ph.check(all(n == want for n in per_tenant.values()),
                 f"every tenant's scored == sent == {want} ({per_tenant})")
        ph.check(dispatches > 0, "scoring.megabatch_dispatches > 0")
        ph.check(ph.out["compiles_after_warmup"] == 0,
                 f"no compile after warm-up, the hot-swap included "
                 f"(got {ph.out['compiles_after_warmup']})")
        ph.check(version == 1 and versions[tids[0]] == 1
                 and all(versions[t] == 0 for t in tids[1:]),
                 f"the swap bumped only its own tenant ({versions})")
        ph.check(seen[tids[0]]["versions"] == {0, 1},
                 "tenant 0 published under the old AND the new weights "
                 f"({sorted(seen[tids[0]]['versions'])})")
        ph.check(all(seen[t]["versions"] == {0} for t in tids[1:]),
                 "no other tenant's batches changed version")
        swapped = pool.stack.get_params(tids[0])
        ph.check(all(np.allclose(a, b) for a, b in zip(
            jax.tree.leaves(swapped), jax.tree.leaves(new_params))),
            "tenant 0's slice of the stack holds the swapped weights")
        _health_checks(ph, rt)
    finally:
        await _stop_feeder(proc)
        await asyncio.wait_for(rt.stop(), 60.0)
    log(f"phase C: {ph.out}")


# -- phases D, E: a sequence model with a context a device in the ring -------

async def phase_context(ph: Phase, expect_platform: str, sizes: Sizes,
                        model: str, configuration: str, devices: int,
                        model_config: dict | None) -> None:
    """`model` at the widths of the benchmark's `configuration` (or at
    `model_config`) behind the gateway at a fleet of `devices`."""
    from sitewhere_tpu.utils.backend import device_memory_bytes

    tid = model.split("-")[0]
    if model_config is None:
        with open(os.path.join(REPO, "benchmarks", "configs",
                               f"{configuration}.json")) as fh:
            model_config = json.load(fh)["model_config"]
    rt = await _start_runtime(f"chip-smoke-{ph.name.lower()}")
    proc = None
    try:
        t_warm = time.monotonic()
        im = rt.services["instance-management"]
        await im.create_tenant(tid, tid, _tenant_sections(
            devices, model, model_config,
            score_dtype="float32", threshold=1e9))
        _seed_history(rt, tid, devices)
        engine = rt.api("rule-processing").engine(tid)
        session = engine.session
        await _wait_warm(session, "session ready for weights")
        limit = device_memory_bytes()
        weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
            jax.eval_shape(session.model.init, jax.random.PRNGKey(0))))
        ph.out.update(weights_bytes=weights, device_bytes=limit,
                      one_set_only=session.one_set_only)
        if limit is not None and 2 * weights > limit:
            ph.check(session.params is None,
                     "a session whose weights fit once holds none until "
                     "the first set is bound")
        engine.swap_model_params(session.model.init(jax.random.PRNGKey(7)))
        await _wait_warm(session, "seeding and warm-up under the weights")
        jax.block_until_ready(session.ring.state)
        ph.out["warmup_s"] = round(time.monotonic() - t_warm, 2)
        log(f"phase {ph.name}: warm in {ph.out['warmup_s']}s")
        # the TPU's compiler is the one held to it: the CPU's copies a
        # table it both gathers from and scatters into round a loop
        _ring_step_checks(ph, session, enforce=expect_platform == "tpu")

        consumer = rt.bus.subscribe(
            rt.naming.tenant_topic(tid, "scored-events"), group="chip-smoke")
        seen = {"events": 0, "versions": set()}
        scored0 = session.flights.latency.count
        warm_mark = ph.compiles()
        proc = await _feed(rt, [tid], devices, sizes)
        want = devices * sizes.ticks

        def progress() -> int:
            _drain_topic(consumer, seen)
            return min(session.flights.latency.count - scored0, seen["events"])

        try:
            await _wait_stream(progress, want, proc)
        except TimeoutError as exc:
            ph.check(False, str(exc))
        rc, sent = await _reap(proc)
        await asyncio.sleep(0.25)
        _drain_topic(consumer, seen)
        consumer.close()
        scored = session.flights.latency.count - scored0
        held = int(rt.metrics.counter("scoring.moe.assignments_held").value)
        stats = jax.local_devices()[0].memory_stats() or {}
        ph.out.update(sent=sent, scored=scored, published=seen["events"],
                      feeder_exit=rc, assignments_held=held,
                      compiles_after_warmup=ph.compiles() - warm_mark,
                      arrays_peak_bytes=stats.get("peak_bytes_in_use"))
        ph.check(rc == 0 and sent == want,
                 f"feeder sent {want} and exited 0 (said {sent}, {rc})")
        ph.check(scored == sent == seen["events"],
                 f"scored == sent == published "
                 f"({scored} / {sent} / {seen['events']})")
        ph.check(held > 0, "token-expert pairs landed on the held experts")
        ph.check(ph.out["compiles_after_warmup"] == 0,
                 f"no compile after warm-up "
                 f"(got {ph.out['compiles_after_warmup']})")
        if session.one_set_only and 2 * weights > limit:
            ph.check(stats["peak_bytes_in_use"] < 1.5 * weights,
                     f"two sets of weights were never resident (arrays' "
                     f"peak {stats['peak_bytes_in_use']}, one set {weights})")
        _health_checks(ph, rt)
    finally:
        await _stop_feeder(proc)
        await asyncio.wait_for(rt.stop(), 60.0)
    log(f"phase {ph.name}: {ph.out}")


# -- the body ----------------------------------------------------------------

async def _run_phases(expect_platform: str, n_devices: int,
                      counter: CompileCounter, sizes: Sizes) -> dict:
    phases = {
        "A": lambda ph: phase_server(ph, expect_platform, sizes),
        "B": lambda ph: phase_kernel(ph, expect_platform, sizes),
        "C": lambda ph: phase_pool(ph, expect_platform, n_devices, sizes),
        "D": lambda ph: phase_context(
            ph, expect_platform, sizes, "dsv3-stream", "deepseek-v3-ep16",
            sizes.dsv3_devices, sizes.dsv3_config),
        "E": lambda ph: phase_context(
            ph, expect_platform, sizes, "laguna-stream", "laguna-s-2.1-ep8",
            sizes.laguna_devices, sizes.laguna_config),
    }
    results = {}
    for name, run in phases.items():
        ph = Phase(name, counter)
        t0 = time.monotonic()
        try:
            await run(ph)
        except Exception as exc:  # noqa: BLE001 - a phase's failure is its result
            traceback.print_exc()
            ph.check(False, f"{type(exc).__name__}: {exc}")
        results[name] = ph.result()
        results[name]["seconds"] = round(time.monotonic() - t0, 2)
    return results


PEAKS = os.path.join(REPO, "benchmarks", "peaks.json")


def peak_bf16_flops(device_kind: str):
    """The chip's peak by `device_kind` as JAX reports it, from the
    benchmark's table (read only), or None for a device not in it."""
    with open(PEAKS) as f:
        entry = json.load(f).get(device_kind)
    return entry and entry["bf16_flops_per_s"]


def require_peak(device_kind: str) -> None:
    """Stop before any phase on a chip the benchmark has no peak for:
    its first run there could report no roofline share."""
    if peak_bf16_flops(device_kind) is None:
        log(f"device_kind {device_kind!r} is not in benchmarks/peaks.json's "
            "peak table; a benchmark here would print mfu: null")
        raise SystemExit(2)


def run_smoke(expect_platform: str, sizes: Sizes) -> dict:
    """Run every phase at `sizes`; returns the summary dict (`ok` is the
    verdict). Raises `SystemExit(2)` before any phase when JAX's first
    device is not `expect_platform`."""
    from sitewhere_tpu.utils.backend import device_summary, use_compile_cache

    cache_dir = use_compile_cache()
    platform, kind, count = device_summary()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    log(f"platform={platform} device_kind={kind} count={count} "
        f"jax={jax.__version__} libtpu={libtpu_version} "
        f"compile_cache={cache_dir}")
    if platform != expect_platform:
        log(f"expected platform {expect_platform!r}, JAX selected "
            f"{platform!r}: not running")
        raise SystemExit(2)
    if platform == "tpu":
        require_peak(kind)

    from sitewhere_tpu.persistence.native import get_lib

    native = get_lib() is not None
    log("native host library: " + ("libswx.so loaded" if native
                                   else "NOT loaded, numpy path"))

    counter = CompileCounter()
    try:
        phases = asyncio.run(asyncio.wait_for(
            _run_phases(expect_platform, count, counter, sizes),
            RUN_DEADLINE_S))
    finally:
        counter.close()
    return {
        "ok": all(p["ok"] for p in phases.values()),
        "device": {"platform": platform, "kind": kind, "count": count},
        "jax": jax.__version__, "libtpu": libtpu_version,
        "compile_cache": cache_dir, "native_library": native,
        "phases": phases,
    }


def main() -> int:
    summary = run_smoke("tpu", FULL)
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": summary["ok"], "device": summary["device"]}),
          flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
