"""Mesh-sharded megabatch serving + self-tuning dispatch (ISSUE 12).

The conftest forces 8 virtual CPU host-platform devices, so every test
here exercises a REAL {data: 4, model: 2} device mesh — sharding
regressions fail in tier-1, not only on TPU rigs.

- wiring/fit: tenant `rule-processing: {mesh}` and the instance
  `scoring_mesh_*` defaults thread to the shared pool; an oversized
  spec fits down to the devices this process has (mesh_from_spec).
- mesh on/off equivalence: identical per-tenant scores, telemetry,
  alerts, and committed offsets under a forced 8-device mesh — the
  sharding changes placement, never behavior.
- hot-swap + add/remove under a SHARDED stack: the donated param swap
  and capacity growth keep the model-axis placement and the version
  fence (attribution never tears).
- self-tuning: the adaptive megabatch window converges under sustained
  signals and never flaps (hysteresis band + cooldown, pinned here).
"""


import jax
import numpy as np
import pytest

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch
from sitewhere_tpu.domain.model import DeviceType
from sitewhere_tpu.kernel.metrics import MetricsRegistry
from sitewhere_tpu.kernel.service import ServiceRuntime
from sitewhere_tpu.models import build_model
from sitewhere_tpu.parallel.mesh import mesh_from_spec
from sitewhere_tpu.persistence.telemetry import TelemetryStore
from sitewhere_tpu.scoring.pool import PoolConfig, SharedScoringPool
from sitewhere_tpu.services import (
    DeviceManagementService,
    DeviceStateService,
    EventManagementService,
    EventSourcesService,
    InboundProcessingService,
    RuleProcessingService,
)
from tests.test_megabatch import (
    RULE,
    TENANTS,
    _batch,
    _drive_tenants,
    megabatch_runtime,
)
from tests.test_pipeline import wait_until

MESH = {"data": 4, "model": 2}


# -- wiring / fit -----------------------------------------------------------

def test_mesh_from_spec_is_exact_or_raises():
    assert jax.device_count() == 8  # the conftest contract
    m = mesh_from_spec(MESH)
    assert dict(m.shape) == {"data": 4, "model": 2}
    # a spec is a budget, not a floor: 2x2 takes the first four devices
    assert dict(mesh_from_spec({"data": 2, "model": 2}).shape) == {
        "data": 2, "model": 2}
    # `data` omitted: every device the model axis divides
    assert dict(mesh_from_spec({"model": 2}).shape) == {"data": 4,
                                                        "model": 2}
    # oversized specs raise, naming the spec and the device count — no
    # shrink, no silent meshless run
    for spec in ({"data": 8, "model": 2}, {"data": 16, "model": 16},
                 {"model": 16}):
        with pytest.raises(ValueError, match="this process has 8"):
            mesh_from_spec(spec)
    # only an empty spec means no mesh (the single-device dispatch)
    assert mesh_from_spec(None) is None
    assert mesh_from_spec({}) is None


def test_mesh_wiring_tenant_and_instance(run):
    async def main():
        # tenant-level `rule-processing: {mesh}` threads to the pool
        async with megabatch_runtime(
                tenants=("t0", "t1"), instance_id="mesh-t",
                rule_extra={"mesh": dict(MESH)}) as rt:
            pool = rt.api("rule-processing").engine("t0").pool_slot.pool
            assert pool.mesh is not None
            assert dict(pool.mesh.shape) == {"data": 4, "model": 2}
            assert rt.metrics.gauge("scoring.mesh_devices:zscore").value == 8
            # stacked params/rings shard the tenant axis over `model`
            # (replicated over `data`): the whole mesh carries state
            assert len(pool.ring.values.sharding.device_set) == 8
        # instance-level defaults reach tenants with no mesh override
        rt = ServiceRuntime(InstanceSettings(
            instance_id="mesh-i", scoring_mesh_data=4,
            scoring_mesh_model=2, scoring_megabatch=True))
        for cls in (DeviceManagementService, EventSourcesService,
                    InboundProcessingService, EventManagementService,
                    DeviceStateService, RuleProcessingService):
            rt.add_service(cls(rt))
        await rt.start()
        try:
            await rt.add_tenant(TenantConfig(
                tenant_id="solo", sections={"rule-processing": dict(RULE)}))
            eng = rt.api("rule-processing").engine("solo")
            assert eng.pool_slot is not None  # instance megabatch engaged
            assert dict(eng.pool_slot.pool.mesh.shape) == {"data": 4,
                                                           "model": 2}
        finally:
            await rt.stop()

    run(main())


# -- mesh on/off equivalence -------------------------------------------------

def test_mesh_on_off_score_equivalence(run):
    """The acceptance pair: a forced 8-device {data: 4, model: 2} mesh
    produces identical per-tenant scores, persisted telemetry, alerts,
    and committed offsets to the meshless stacked dispatch."""
    async def main():
        async with megabatch_runtime(instance_id="mesh-on",
                                     rule_extra={"mesh": dict(MESH)}) as rt:
            on = await _drive_tenants(rt)
            assert rt.metrics.gauge("scoring.mesh_devices:zscore").value == 8
            assert rt.metrics.counter(
                "scoring.megabatch_dispatches").value > 0
        async with megabatch_runtime(instance_id="mesh-off") as rt:
            off = await _drive_tenants(rt)
            assert rt.metrics.gauge("scoring.mesh_devices:zscore").value == 0
        for tid in TENANTS:
            scored_on, total_on, alerts_on, committed_on = on[tid]
            scored_off, total_off, alerts_off, committed_off = off[tid]
            assert total_on == total_off == 32 * 10
            assert scored_on.keys() == scored_off.keys()
            for key, val in scored_on.items():
                assert scored_off[key] == val, (tid, key)
            assert alerts_on == alerts_off and alerts_on
            assert committed_on == committed_off > 0

    run(main())


# -- hot-swap + add/remove under a sharded stack -----------------------------

def test_sharded_hot_swap_and_add_remove(run):
    """The lifecycle edge the mesh must survive: a donated param swap
    mid-flight keeps the dispatch's attribution (version fence), stack
    growth re-places shards, and a removed tenant's slot reuse leaks
    nothing — all with the tenant axis live on the `model` mesh axis."""
    async def main():
        metrics = MetricsRegistry()
        model = build_model("lstm", window=16, hidden=8)
        mesh = mesh_from_spec(MESH)
        pool = SharedScoringPool(
            model, metrics, PoolConfig(batch_buckets=(32,),
                                       batch_window_ms=50.0),
            mesh=mesh)
        got: dict[str, int] = {}

        def deliver_for(tid):
            async def deliver(scored):
                got[tid] = got.get(tid, 0) + len(scored)
            return deliver

        delivered: list = []

        async def capture(scored):
            delivered.append(scored)

        pool.register("a", TelemetryStore(history=32), 6.0, capture)
        pool.register("b", TelemetryStore(history=32), 6.0,
                      deliver_for("b"))
        await wait_until(lambda: pool.ready, timeout=120.0)
        # params live sharded: the stacked leaves span the mesh
        leaf = jax.tree.leaves(pool.stack.stacked)[0]
        assert len(leaf.sharding.device_set) == 8
        # dispatch, then swap mid-flight: the settled batch must carry
        # the DISPATCH-time version (the fence), sharded or not
        pool.admit("a", _batch("a"))
        pool._flush_round()
        v = pool.stack.set_params("a", model.init(jax.random.PRNGKey(7)))
        assert v == 1
        await wait_until(lambda: len(delivered) == 1, timeout=60.0)
        assert delivered[0].model_version == 0
        # the donated swap kept the placement
        leaf = jax.tree.leaves(pool.stack.stacked)[0]
        assert len(leaf.sharding.device_set) == 8
        # grow: a third tenant crosses the 2-capacity bucket → 4 rows
        # (model-axis multiples), re-placed, rebuild counted
        pool.register("c", TelemetryStore(history=32), 6.0,
                      deliver_for("c"))
        assert pool.stack.capacity == 4
        assert metrics.counter("scoring.stack_rebuilds").value >= 1
        leaf = jax.tree.leaves(pool.stack.stacked)[0]
        assert len(leaf.sharding.device_set) == 8
        await wait_until(lambda: pool.ready, timeout=120.0)
        # remove b (pending accounted dropped), the rest keep scoring
        pool.admit("b", _batch("b", t=20.0))
        pool.unregister("b")
        assert metrics.counter("scoring.admissions_dropped").value >= 8
        for tid in ("a", "c"):
            pool.admit(tid, _batch(tid, t=21.0))
        pool._flush_round()
        await wait_until(lambda: len(delivered) == 2
                         and got.get("c") == 8, timeout=60.0)
        assert delivered[1].model_version == 1  # post-swap attribution
        pool.close()

    run(main())


# -- adaptive megabatch window ----------------------------------------------

def _tuned_pool(window_auto=True):
    return SharedScoringPool(
        build_model("zscore", window=8), MetricsRegistry(),
        PoolConfig(batch_buckets=(32,), batch_window_ms=2.0,
                   window_auto=window_auto))


def _drive_tuner(pool, rounds, packed, live):
    """Simulate `rounds` flush rounds each packing `packed` tenants
    while the tenants in `live` keep admitting (the signal `admit`
    feeds the tuner)."""
    for _ in range(rounds):
        pool._tuner_tenants.update(live)
        pool._tune_window(packed)


def test_window_autotune_converges_and_never_flaps():
    pool = _tuned_pool()
    live = [f"t{i}" for i in range(8)]
    base = pool.cfg.window_s
    adjusts = pool.window_adjusts
    # chronically under-packed rounds (2 of 8 live tenants per
    # dispatch): the window widens to the 8× bound and STAYS there
    _drive_tuner(pool, 200, packed=2, live=live)
    assert pool._window_s == pytest.approx(base * pool.WINDOW_SPAN)
    at_bound = adjusts.value
    _drive_tuner(pool, 200, packed=2, live=live)
    assert adjusts.value == at_bound  # pinned, not flapping
    # full packs: narrows back to the configured floor and holds
    _drive_tuner(pool, 600, packed=8, live=live)
    assert pool._window_s == pytest.approx(base)
    at_floor = adjusts.value
    _drive_tuner(pool, 200, packed=8, live=live)
    assert adjusts.value == at_floor
    # the hysteresis band [0.5, 0.9]: mid occupancy moves nothing
    _drive_tuner(pool, 200, packed=6, live=live)  # 0.75 of 8
    assert adjusts.value == at_floor
    assert pool._window_s == pytest.approx(base)
    pool.close()


def test_window_autotune_off_pins_window():
    pool = _tuned_pool(window_auto=False)
    _drive_tuner(pool, 200, packed=1, live=[f"t{i}" for i in range(8)])
    assert pool._window_s == pool.cfg.window_s
    assert pool.window_adjusts.value == 0
    pool.close()


def test_window_autotune_idle_tenants_dont_pin_the_cap():
    """Registered-but-idle tenants must not drag occupancy down: a pool
    with 8 registered tenants where only ONE sends traffic holds the
    configured floor (a wider window could aggregate nothing), instead
    of ratcheting to 8× and taxing the lone active tenant's latency."""
    pool = _tuned_pool()
    pool.tenants = {f"t{i}": object() for i in range(8)}  # registered
    _drive_tuner(pool, 200, packed=1, live=["t0"])  # one live tenant
    assert pool._window_s == pool.cfg.window_s
    assert pool.window_adjusts.value == 0
    # several live tenants that never share a round DO earn a wider
    # window (1 of 3 packed = 0.33, under the 0.5 widen threshold)
    _drive_tuner(pool, 200, packed=1, live=["t0", "t1", "t2"])
    assert pool._window_s > pool.cfg.window_s
    pool.close()


# -- the chaos seam ----------------------------------------------------------

def test_mesh_chaos_quarantines_with_provenance(run):
    """An injected `scoring.mesh` fault at admission dead-letters the
    admitting record (same contract as scoring.megabatch); the sharded
    pool survives and later records score normally."""
    async def main():
        from sitewhere_tpu.kernel.bus import TopicNaming
        from sitewhere_tpu.kernel.dlq import list_dead_letters
        from sitewhere_tpu.kernel.faults import FaultInjector

        fi = FaultInjector(seed=9)
        async with megabatch_runtime(tenants=("t0",), faults=fi,
                                     instance_id="mesh-ch",
                                     rule_extra={"mesh": dict(MESH)}) as rt:
            fi.arm("scoring.mesh", rate=1.0, max_faults=1)
            decoded = rt.naming.tenant_topic(
                "t0", TopicNaming.EVENT_SOURCE_DECODED)
            dlq = rt.naming.tenant_topic("t0", TopicNaming.DEAD_LETTER)
            await rt.bus.produce(decoded, _batch("t0", n=16, t=1000.0),
                                 key="gw")
            await wait_until(
                lambda: len(list_dead_letters(rt.bus, dlq)) >= 1,
                timeout=15.0)
            entries = list_dead_letters(rt.bus, dlq)
            assert entries[0][1]["original_topic"] == decoded
            # spent: the next record scores through the mesh normally
            scored_topic = rt.naming.tenant_topic(
                "t0", TopicNaming.SCORED_EVENTS)
            consumer = rt.bus.subscribe(scored_topic, group="mesh-ch-m")
            await rt.bus.produce(decoded, _batch("t0", n=16, t=1060.0),
                                 key="gw")
            seen: list = []

            def collect():
                seen.extend(consumer.poll_nowait(max_records=64))
                return sum(len(r.value) for r in seen) >= 16
            await wait_until(collect, timeout=15.0)
            consumer.close()

    run(main())
