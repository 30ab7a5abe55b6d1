#!/usr/bin/env bash
# The tier-1 verification gate, runnable identically by builders and
# reviewers. Three steps:
#   1. a compileall syntax smoke over the package (fails fast on a file
#      that only breaks at import time), then
#   2. `swx lint` (the AST invariant checker, docs/ANALYSIS.md) — new
#      findings fail the gate before a single test runs, then
#   3. the ROADMAP.md "Tier-1 verify" command VERBATIM — keep the block
#      below byte-identical to ROADMAP.md so both audiences run the same
#      gate. The pytest sweep includes the fastlane lane-equivalence
#      suite (tests/test_fastlane.py, unmarked = default tier): the
#      fused ingress path must stay behaviorally identical to the
#      staged lane (docs/PERFORMANCE.md) for the gate to pass.
cd "$(dirname "$0")/.."

python -m compileall -q sitewhere_tpu || exit 1

# `swx lint --format json` without the CLI entrypoint dependency; the
# JSON report is the CI artifact (exit 1 = new findings or stale
# baseline entries, see output), and the per-code summary below is the
# one-line gate digest reviewers read
python -m sitewhere_tpu.analysis --format json > /tmp/_swxlint.json || { cat /tmp/_swxlint.json; echo "swxlint: new findings or stale baseline (see JSON above; docs/ANALYSIS.md)"; exit 1; }
python - <<'PY' || exit 1
import json
d = json.load(open("/tmp/_swxlint.json"))
per = {}
for kind in ("findings", "baselined", "suppressed"):
    for f in d[kind]:
        per.setdefault(f["code"], dict.fromkeys(
            ("findings", "baselined", "suppressed"), 0))[kind] += 1
cols = "  ".join(
    f"{code}:{c['findings']}/{c['baselined']}/{c['suppressed']}"
    for code, c in sorted(per.items())) or "all codes clean"
total = sum(d["timings_s"].values())
slowest = max(d["timings_s"].items(), key=lambda kv: kv[1])
print(f"swxlint per-code (new/baselined/suppressed): {cols}")
print(f"swxlint timings: {total:.2f}s total, slowest "
      f"{slowest[0]}={slowest[1]:.2f}s over {d['checked_files']} files")
PY

# forced-multi-device smoke (docs/PERFORMANCE.md mesh serving): a REAL
# 8-device {data: 4, model: 2} host-platform mesh must shard the
# stacked dispatch and survive a donated hot-swap — sharding
# regressions fail here in tier-1, not only on TPU rigs. (The pytest
# sweep below runs under the same 8-virtual-device conftest; this
# smoke keeps the contract visible even if conftest ever changes.)
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY' || { echo "mesh smoke: FAILED (sharded stacked dispatch broken)"; exit 1; }
import jax, numpy as np
jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, jax.devices()
from sitewhere_tpu.models import build_model
from sitewhere_tpu.parallel.mesh import mesh_from_spec
from sitewhere_tpu.parallel.tenant_stack import TenantStack
from sitewhere_tpu.scoring.ring import StackedDeviceRing

mesh = mesh_from_spec({"data": 4, "model": 2})
assert dict(mesh.shape) == {"data": 4, "model": 2}
model = build_model("zscore", window=8)
stack = TenantStack(model, mesh=mesh)
for tid in ("a", "b", "c"):
    stack.add_tenant(tid)
ring = StackedDeviceRing(8, stack.capacity, device_cap=32, mesh=mesh)
b = stack.pad_batch(16)
dev = np.full((stack.capacity, b), ring.device_cap, np.int32)
val = np.zeros((stack.capacity, b), np.float32)
dev[0, :4] = np.arange(4); val[0, :4] = 21.0
scores = ring.update_and_score(model, stack.stacked, dev, val)
assert scores.shape == (stack.capacity, b), scores.shape
assert len(scores.sharding.device_set) == 8, scores.sharding
stack.set_params("b", model.init(jax.random.PRNGKey(1)))  # donated swap
assert stack.versions["b"] == 1
# model-axis placement survives growth + swap: ring state spans the mesh
assert len(ring.values.sharding.device_set) == 8, ring.values.sharding
np.asarray(ring.update_and_score(model, stack.stacked, dev, val))
print("mesh smoke: OK (8-device {data:4, model:2} stacked dispatch)")
PY

# wire fast-path smoke (docs/PERFORMANCE.md wire fast path): one REAL
# 2-process poll/produce round in streaming-prefetch mode — a broker
# process (BusServer) and a consumer OS process (RemoteEventBus,
# prefetch + pipelined produce on) exchange records over a socket; the
# consumer must receive every record via pushed deliver frames (zero
# poll RPCs), commit, and ack back through the coalesced produce path.
env JAX_PLATFORMS=cpu python - <<'PY' || { echo "wire smoke: FAILED (prefetch data plane broken across processes)"; exit 1; }
import asyncio, os, subprocess, sys

CONSUMER = r'''
import asyncio, sys
sys.path.insert(0, ".")

async def main():
    from sitewhere_tpu.kernel.wire import RemoteEventBus
    remote = RemoteEventBus("127.0.0.1", int(sys.argv[1]),
                            prefetch=True, prefetch_credit=16)
    await remote.initialize()
    orig_call = remote._client.call  # spy: no poll RPCs may be issued
    issued = []
    async def spying_call(op, *a, **kw):
        issued.append(op)
        return await orig_call(op, *a, **kw)
    remote._client.call = spying_call
    consumer = remote.subscribe("smoke", group="g")
    got = []
    while len(got) < 20:
        got += [r.value["i"] for r in await consumer.poll(
            max_records=8, timeout=5.0)]
    assert sorted(got) == list(range(20)), got
    assert "poll" not in issued, f"prefetch mode issued poll RPCs: {issued}"
    consumer.commit()
    remote.produce_nowait("smoke-ack", {"ok": True, "n": len(got)})
    await remote.stop()  # flushes the coalesced batch before close
    print("CONSUMER-OK", flush=True)

asyncio.run(main())
'''

async def main():
    from sitewhere_tpu.kernel.bus import EventBus
    from sitewhere_tpu.kernel.wire import BusServer
    bus = EventBus(default_partitions=2)
    server = BusServer(bus)
    await server.start()
    for i in range(20):
        await bus.produce("smoke", {"i": i}, key=f"k{i % 4}")
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-c", CONSUMER, str(server.port),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    out, err = await asyncio.wait_for(proc.communicate(), 120.0)
    assert proc.returncode == 0, err.decode()[-2000:]
    assert b"CONSUMER-OK" in out
    ack = bus.peek("smoke-ack", limit=10)
    assert ack and ack[-1].value == {"ok": True, "n": 20}, ack
    committed = bus._groups["g"].committed
    assert sum(committed.values()) == 20, committed
    await server.stop()
    print("wire smoke: OK (2-process prefetch round, 0 poll RPCs, "
          "batched ack)")

asyncio.run(main())
PY

# predictive-control smoke (docs/FLEET.md predictive control): a tiny
# forecaster trained from synthetic telemetry history must deploy
# through the version-fenced tenant-0 slot on the shared scoring pool
# and yield ONE forecast-attributed autoscale decision — the training
# → checkpoint → serve → decide spine fails here in tier-1, not only
# in the ramp drill.
env JAX_PLATFORMS=cpu python - <<'PY' || { echo "forecast smoke: FAILED (predictive control plane broken)"; exit 1; }
import asyncio, math, tempfile, time
from types import SimpleNamespace
import jax
jax.config.update("jax_platforms", "cpu")
from sitewhere_tpu.config import InstanceSettings
from sitewhere_tpu.fleet.controller import AutoscalerPolicy
from sitewhere_tpu.fleet.forecast import PredictivePlanner
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.persistence.durable import TelemetryHistory

WS = 1.0
tmp = tempfile.mkdtemp(prefix="swx-forecast-smoke-")
h = TelemetryHistory(tmp + "/hist", window_s=WS)
t0 = math.floor(time.time() / WS) * WS - 60 * WS
for i in range(58):  # a clean per-tenant load ramp, 1s windows
    for tid in ("acme", "beta"):
        h.append(tid, "lag", 40.0 * i, t=t0 + i * WS + 0.5)
h.flush()
settings = InstanceSettings(
    data_dir=tmp + "/data", fleet_forecast_window=16,
    fleet_forecast_horizon_s=4.0, fleet_forecast_interval_s=0.0,
    fleet_forecast_min_windows=6)
runtime = SimpleNamespace(settings=settings, metrics=MetricsRegistry(),
                          history=h, tracer=None, faults=None)
c = SimpleNamespace(runtime=runtime,
                    policy=AutoscalerPolicy(scale_up_lag=300.0,
                                            cooldown_s=0.0),
                    tenants={"acme": object(), "beta": object()},
                    _last_scale_t=-1e9, _pending_spawns=0)
planner = PredictivePlanner(c)
report = planner.train_from_history(steps=25)
assert report is not None and report["version"] >= 1, report

async def main():
    await planner.tick()  # starts tenant-0 serving + backfills
    deadline = time.monotonic() + 60.0
    while not planner.forecasts and time.monotonic() < deadline:
        wall = time.time()
        i = (wall - t0) / WS
        for tid in ("acme", "beta"):
            h.append(tid, "lag", 40.0 * i, t=wall)
        await planner.tick()
        await asyncio.sleep(0.25)
    return planner.decide({"w1": 1.0}, {})

d = asyncio.run(main())
try:
    assert d is not None and d["action"] == "add_replica", \
        (d, planner.snapshot())
    assert d["reason"].startswith("forecast:"), d
    assert d["forecast"]["predicted_load"] > 0, d
finally:
    planner.close()
    h.close()
print("forecast smoke: OK (trained v%d, one forecast-attributed "
      "autoscale decision)" % report["version"])
PY

# replay smoke (docs/PERFORMANCE.md replay plane): ingest → compact →
# replay must run the REAL spine — durable segments fold into column
# blocks, the ReplayEngine streams them through an actual
# SharedScoringPool megabatch slot, and the shadow-scoring gate must
# CATCH a perturbed candidate checkpoint (and promote an equivalent
# one) — the cold-tier → scoring-plane contract fails here in tier-1.
env JAX_PLATFORMS=cpu python - <<'PY' || { echo "replay smoke: FAILED (ingest→compact→replay→gate spine broken)"; exit 1; }
import asyncio, os, tempfile
import jax, numpy as np
jax.config.update("jax_platforms", "cpu")
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.history import (DivergenceGateError, EventHistoryStore,
                                   ReplayEngine, ScoreCollector)
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.models.registry import build_model
from sitewhere_tpu.persistence.durable import RT_MEASUREMENTS, SegmentLog
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.pool import PoolConfig, SharedScoringPool

tmp = tempfile.mkdtemp(prefix="swx-replay-smoke-")
log = SegmentLog(os.path.join(tmp, "events"), segment_bytes=1 << 14)
rng = np.random.default_rng(11)
N, D, t0 = 4000, 48, 1_700_000_000.0
for i in range(8):
    n = N // 8
    dev = rng.integers(0, D, n).astype(np.uint32)
    ts = (t0 + i * 5.0 + np.sort(rng.random(n) * 5.0)).astype(np.float64)
    val = rng.normal(20.0, 5.0, n).astype(np.float32)
    log.append(RT_MEASUREMENTS, MeasurementBatch(
        BatchContext("acme"), dev, np.zeros(n, np.uint16), val,
        ts).encode())
log.close()
m = MetricsRegistry()
store = EventHistoryStore(os.path.join(tmp, "history"), source=log,
                          window_s=10.0, metrics=m)
rep = store.compact(through_seq=log._seq)
assert rep["events"] == N and rep["tail_skips"] == 0, rep

async def sink(s):
    pass

async def main():
    pool = SharedScoringPool(build_model("lstm", window=16, hidden=8), m,
                             PoolConfig(batch_buckets=(256, 2048),
                                        batch_window_ms=1.0))
    eng = ReplayEngine(pool, metrics=m)
    col = ScoreCollector()
    r = await eng.replay("acme", store, 6.0, collect=col)
    assert r["events"] == col.total == N, r
    slot = pool.register("acme", TelemetryStore(), 6.0, sink)
    live = pool.stack.get_params("acme")
    try:
        await eng.guard_swap(slot, store,
                             jax.tree.map(lambda a: a + 0.5, live),
                             max_divergence=0.05)
        raise AssertionError("perturbed candidate was NOT caught")
    except DivergenceGateError as e:
        assert e.report["max_abs"] > 0.05, e.report
    v, g = await eng.guard_swap(slot, store, live, max_divergence=0.05)
    assert g["promoted"] and g["max_abs"] == 0.0, g
    pool.close()
    return g

g = asyncio.run(main())
snap = m.snapshot()
assert snap["history.compactions"] >= 1
assert snap["history.replay_events"] >= 3 * N  # replay + two gate legs
print("replay smoke: OK (%d events compacted+replayed, perturbed "
      "candidate caught, equivalent candidate promoted)" % N)
PY

# fleet-observe smoke (docs/OBSERVABILITY.md fleet observability): a
# 2-worker trace must stitch end-to-end — ONE origin-scoped trace id
# whose spine (receive → wire hop → enrich → persist → dispatch →
# score → publish) crosses REAL worker processes over the wire bus,
# with the FleetObserver's merged critical path covering the worker
# side. Marked `slow` so the bare ROADMAP tier-1 sweep (which runs
# `-m 'not slow'`) doesn't pay the two jax-bearing subprocesses twice;
# THIS gate runs it explicitly.
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_observe.py -q -m slow -p no:cacheprovider || { echo "fleet-observe smoke: FAILED (2-worker trace does not stitch end-to-end)"; exit 1; }

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
