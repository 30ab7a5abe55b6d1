"""Fleet worker process entry: `python -m sitewhere_tpu.fleet.worker_main
'<json-config>'` (or `swx fleet-worker`, cli.py).

The config is one JSON object:

    {"worker_id": "w0", "host": "127.0.0.1", "port": 47900,
     "instance_id": "swx1",            # MUST match the broker's naming
     "force_cpu": false,
     "secret": null,                   # wire-auth shared secret
     "settings": {...},                # InstanceSettings overrides
     "chaos": {"seed": 0,              # optional fault injection
               "sites": {"fleet.heartbeat": {"rate": 0.5,
                                             "max_faults": 1}}}}

Builds a `fleet_managed` ServiceRuntime over a `RemoteEventBus` with the
scoring-pipeline services (the colocation set the split topology
proved: device-mgmt, inbound, event-mgmt, device-state,
rule-processing), attaches a `FleetWorker`, and runs until SIGTERM/
SIGINT or until the controller retires the worker.

Hermetic by default: tenant registry state replicates over the bus
(the per-tenant registry-state topic, services/replication.py), so a
worker needs NOTHING but the wire broker to adopt a tenant — no shared
filesystem. A `data_dir`, when given, is worker-LOCAL (registry WAL +
snapshots for single-node restart; event-history spill), never shared.
Every data-path produce/commit carries the placement epoch fencing
token; a worker whose writes are rejected (it was declared dead while
stalled) stops the tenant's engines instead of retrying
(docs/FLEET.md fencing protocol).
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import sys


def build_runtime(cfg: dict):
    """Worker runtime from a config dict (bench + CLI entry share it)."""
    from sitewhere_tpu.config import InstanceSettings
    from sitewhere_tpu.fleet.worker import FleetWorker
    from sitewhere_tpu.kernel.service import ServiceRuntime
    from sitewhere_tpu.kernel.wire import RemoteEventBus
    from sitewhere_tpu.services import (
        DeviceManagementService,
        DeviceStateService,
        EventManagementService,
        InboundProcessingService,
        RuleProcessingService,
    )

    settings = InstanceSettings(
        instance_id=cfg["instance_id"], fleet_managed=True,
        **(cfg.get("settings") or {}))
    # wire data-plane fast path (docs/PERFORMANCE.md): prefetch +
    # pipelined produce ride the same settings overlay as every other
    # knob
    bus = RemoteEventBus(cfg.get("host", "127.0.0.1"), cfg["port"],
                         secret=cfg.get("secret"),
                         prefetch=settings.wire_prefetch,
                         prefetch_credit=settings.wire_prefetch_credit,
                         pipeline=settings.wire_pipeline,
                         linger_ms=settings.wire_linger_ms,
                         inflight_cap=settings.wire_inflight_cap)
    # owner-tag every membership this worker registers: a controller
    # death declaration then evicts them broker-side, so a SIGSTOPped
    # zombie's partitions reassign instead of stalling until SIGCONT
    bus.owner = cfg["worker_id"]
    rt = ServiceRuntime(settings, bus=bus)
    for cls in (DeviceManagementService, InboundProcessingService,
                EventManagementService, DeviceStateService,
                RuleProcessingService):
        rt.add_service(cls(rt))
    worker = FleetWorker(rt, cfg["worker_id"])
    rt.add_child(worker)
    chaos = cfg.get("chaos")
    if chaos:
        from sitewhere_tpu.kernel.faults import FaultInjector

        injector = FaultInjector(seed=int(chaos.get("seed", 0)))
        sites = chaos.get("sites") or {}
        # literal site names only (FLT01: the registry vouches for
        # literals) — the worker-side chaos surfaces are the heartbeat
        # loop and the replay-on-adopt path; bus.poll rides the broker
        # process, not this one
        spec = sites.get("fleet.heartbeat")
        if spec:
            injector.arm("fleet.heartbeat",
                         rate=float(spec.get("rate", 1.0)),
                         max_faults=int(spec.get("max_faults", -1)))
        spec = sites.get("fence.adopt")
        if spec:
            injector.arm("fence.adopt",
                         rate=float(spec.get("rate", 1.0)),
                         max_faults=int(spec.get("max_faults", -1)))
        rt.install_faults(injector)
    return rt, worker


async def amain(cfg: dict) -> int:
    rt, worker = build_runtime(cfg)
    await rt.start()
    api = None
    if cfg.get("api_port") is not None:
        # per-worker control/query plane (kernel/wire.py ApiServer):
        # observe/trace/health ops for fleet tooling — the trace op is
        # how a cross-process trace is stitched (tests, tier1 smoke)
        from sitewhere_tpu.kernel.wire import ApiServer

        api = ApiServer(rt, port=int(cfg["api_port"]),
                        secret=cfg.get("secret"))
        await api.start()
        print(f"FLEET-WORKER {cfg['worker_id']} api-port {api.port}",
              flush=True)
    print(f"FLEET-WORKER {cfg['worker_id']} up", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # pragma: no cover
            pass
    while not stop.is_set() and not worker.retired:
        try:
            await asyncio.wait_for(stop.wait(), timeout=0.25)
        except asyncio.TimeoutError:
            pass
    if worker.retired:
        print(f"FLEET-WORKER {cfg['worker_id']} retired", flush=True)
    if api is not None:
        await api.stop()
    await rt.stop()
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: python -m sitewhere_tpu.fleet.worker_main "
              "'<json-config>'", file=sys.stderr)
        return 2
    cfg = json.loads(argv[0])
    if cfg.get("force_cpu"):
        import jax

        jax.config.update("jax_platforms", "cpu")
    from sitewhere_tpu.utils.backend import use_compile_cache

    # a replacement worker adopting a tenant mid-run must not pay the
    # full first-compile on shapes the fleet already compiled: every
    # worker of a checkout resolves the same cache directory
    use_compile_cache()
    import logging

    logging.basicConfig(
        level=getattr(logging, str(cfg.get("log_level", "INFO")).upper(),
                      logging.INFO),
        format=f"%(asctime)s [{cfg.get('worker_id', '?')}] "
               f"%(name)s %(levelname)s %(message)s")
    return asyncio.run(amain(cfg))


if __name__ == "__main__":
    sys.exit(main())
