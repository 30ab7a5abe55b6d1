"""benchmarks/run.py's body, tiny, on the conftest's virtual CPU devices:
each loop kind, a traced run, a meshed four-chip configuration from data
files alone, the refusal off the chip, and `correct` coming out false
with the timed path broken underneath (one run for each fault a serving
cell can have).
"""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
SEED = 2 ** 31 + 17          # the driver's seeds are large


@pytest.fixture(autouse=True)
def jax_config_left_as_found(monkeypatch, tmp_path):
    """`run_cell` places the compile cache and keeps every program in it.
    Here the cache is placed from outside, so the program's helper sets
    nothing, and the two thresholds are put back after the test."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


@pytest.fixture(autouse=True)
def one_settle_thread(monkeypatch):
    """The program publishes in the order its read-backs finish (PERF.md,
    section 7, fault 2). On the CPU, where two flushes in flight run side
    by side on eight settle threads, that order is a race; one settle
    thread makes it the dispatch order, so that these rehearsals test the
    harness and not that race."""
    from concurrent.futures import ThreadPoolExecutor

    from sitewhere_tpu.scoring import pool, server

    one = ThreadPoolExecutor(max_workers=1, thread_name_prefix="settle-1")
    monkeypatch.setattr(server, "SETTLE_POOL", one)
    monkeypatch.setattr(pool, "SETTLE_POOL", one)
    yield
    one.shutdown(wait=False)


def tiny_tree(dst, devices=64, tenants=2, mesh=None):
    """A copy of the benchmark's data with every fleet cut to `devices`
    (behind gateways of 16 where the configuration has gateways), and
    three more configurations added with their cells as a later PR would
    add them, as files and manifest entries and no code: the pooled one
    (`pool-100k` is no cell of BENCHMARK.json: on one chip it holds too
    little of it, PERF.md section 4), with `mesh` a meshed four-chip one,
    and `stream-cold`, a fleet with no history that the served path
    itself warms (nine ticks of warm-up beats)."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    for path in (dst / "benchmarks" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(devices_per_tenant=devices, anomaly_rate=0.02,
                   tenants=min(cfg["tenants"], tenants))
        if "frame_devices" in cfg:
            cfg["frame_devices"] = 16
        path.write_text(json.dumps(cfg))

    def add(name, cfg, chips):
        m["configs"].append({
            "name": name, "source": cfg["source"], "reduced": [],
            "file": f"benchmarks/configs/{name}.json", "why": "added"})
        m["workloads"].append({
            "name": f"{name}.steady", "config": name, "traffic": "steady",
            "chips": chips, "why": "added"})
        for e in m["end_to_end"] + m["per_layer"]:
            if "stream-512k.steady" in e.get("workloads", []):
                e["workloads"].append(f"{name}.steady")

    pool = json.loads((dst / "benchmarks/configs/pool-100k.json").read_text())
    add("pool-100k", pool, 1)
    if mesh:
        pool.update(name="pool-100k-mesh4", mesh=mesh, chips=4, tenants=4)
        (dst / "benchmarks/configs/pool-100k-mesh4.json").write_text(
            json.dumps(pool))
        shutil.copy(dst / "benchmarks/traffic/pool-100k.steady.json",
                    dst / "benchmarks/traffic/pool-100k-mesh4.steady.json")
        add("pool-100k-mesh4", pool, 4)
    cold = json.loads((dst / "benchmarks/configs/stream-512k.json").read_text())
    cold.update(name="stream-cold", history_ticks=0)
    (dst / "benchmarks/configs/stream-cold.json").write_text(json.dumps(cold))
    traffic = json.loads(
        (dst / "benchmarks/traffic/stream-512k.steady.json").read_text())
    traffic["warm_beats"] = 9 * devices // 16
    (dst / "benchmarks/traffic/stream-cold.steady.json").write_text(
        json.dumps(traffic))
    add("stream-cold", cold, 1)
    (dst / "BENCHMARK.json").write_text(json.dumps(m))
    return str(dst)


def frames_a_second(cell="stream-512k.steady"):
    with open(os.path.join(ROOT, "benchmarks", "traffic", f"{cell}.json")) as fh:
        return json.load(fh)["frames_per_s"]


def tiny_run(tmp_path, cell, trace=False, **tree):
    return run.run_cell(cell, SEED, 1.0, trace, "cpu",
                        root=tiny_tree(tmp_path, **tree))


def well_formed(result, manifest_kind, root, cell):
    assert list(result)[:5] == RESULT_KEYS and list(result)[-1] == "checks"
    assert set(result) - {"breakdown"} == set(RESULT_KEYS) | {"checks"}
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(set(c) == {"value", "limit"} for c in result["checks"].values())
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    declared = {e["name"]: e["unit"] for e in m[manifest_kind]
                if cell in e.get("workloads", [cell])}
    for name, got in result["metrics"].items():
        assert got["unit"] == declared[name]
        assert np.isfinite(got["value"]) and got["value"] > 0
    return declared


def test_open_loop_cell_tiny_on_cpu(tmp_path):
    result, info = tiny_run(tmp_path, "stream-512k.steady")
    assert result["correct"], result["checks"]
    declared = well_formed(result, "end_to_end", str(tmp_path),
                           "stream-512k.steady")
    # the tails are per-layer metrics (PERF.md, section 2)
    assert set(result["metrics"]) == set(declared) == {
        "events_per_s", "latency_p50_ms", "setup_s"}
    frames = frames_a_second()          # a window of one second
    assert info["frames"] == frames and info["rejected_events"] == 0
    assert result["attempted"] == frames * 16
    # four gateways of 16, four warm-up beats: every frame is compared
    assert info["compared_events"] == (4 + frames) * 16


def test_closed_loop_cell_traced_tiny_on_cpu(tmp_path):
    result, info = tiny_run(tmp_path, "stream-512k.saturate", trace=True)
    assert result["correct"], result["checks"]
    declared = well_formed(result, "per_layer", str(tmp_path),
                           "stream-512k.saturate")
    # what the counters and the client's clock give is there; a CPU trace
    # has no device plane, so its readers return nothing: left out, not 0
    assert {"events_per_dispatch", "saturate_latency_p95_ms"} \
        == set(result["metrics"]) < set(declared)
    assert "breakdown" not in result and "busy_s" not in result["device"]
    assert info["frames"] > 10
    assert not os.path.exists(tmp_path / ".bench_trace" / "stream-512k.saturate")


def test_cold_fleet_cell_tiny_on_cpu(tmp_path):
    """No history in the store: the ring starts from zero state and the
    warm-up beats open the model's 8-reading gate before the window."""
    result, info = tiny_run(tmp_path, "stream-cold.steady")
    assert result["correct"], result["checks"]
    well_formed(result, "end_to_end", str(tmp_path), "stream-cold.steady")
    frames = frames_a_second()
    assert info["frames"] == frames and info["rejected_events"] == 0
    assert info["compared_events"] == (36 + frames) * 16


def test_pooled_cell_tiny_on_cpu(tmp_path):
    result, info = tiny_run(tmp_path, "pool-100k.steady")
    assert result["correct"], result["checks"]
    declared = well_formed(result, "end_to_end", str(tmp_path),
                           "pool-100k.steady")
    assert set(result["metrics"]) == set(declared) == {
        "events_per_s", "latency_p50_ms", "setup_s"}
    assert info["frames"] == 20 and result["attempted"] == 20 * 2 * 64


def test_meshed_four_chip_cell_from_data_files_alone(tmp_path):
    result, info = tiny_run(tmp_path, "pool-100k-mesh4.steady",
                            mesh={"data": 2, "model": 2})
    assert result["correct"], result["checks"]
    well_formed(result, "end_to_end", str(tmp_path), "pool-100k-mesh4.steady")
    assert info["mesh"] == {"data": 2, "model": 2}
    assert result["device"]["count"] >= 4


def test_refuses_the_wrong_platform_and_too_few_chips(tmp_path):
    root = tiny_tree(tmp_path)
    with pytest.raises(SystemExit) as exc:
        run.run_cell("stream-512k.steady", SEED, 1.0, False, "tpu", root=root)
    assert exc.value.code not in (0, None)
    with pytest.raises(SystemExit):
        run.run_cell("no-such-cell", SEED, 1.0, False, "cpu", root=root)


def _state_unchanged(monkeypatch):
    """A step that returns its state as it got it."""
    from sitewhere_tpu.scoring import stream

    real = stream.streaming_step

    def broken(model, out_dtype=None):
        step = real(model, out_dtype)
        return lambda params, state, dev, v: (state, step(
            params, state, dev, v)[1])

    monkeypatch.setattr(stream, "streaming_step", broken)
    return "score_gap_mean"


def _answer_altered(monkeypatch):
    """A score altered where it is produced."""
    from sitewhere_tpu.models.lstm import StreamingLstmModel

    real = StreamingLstmModel.step_score

    def broken(self, params, rows, v):
        score, out = real(self, params, rows, v)
        return score * 1.02, out

    monkeypatch.setattr(StreamingLstmModel, "step_score", broken)
    return "score_gap_max"


def _half_left_out(monkeypatch):
    """Half of every admitted batch left out of scoring."""
    from sitewhere_tpu.scoring.server import ScoringSession

    real = ScoringSession.admit

    def broken(self, batch):
        half = np.arange(len(batch)) < len(batch) // 2
        return real(self, batch.select(half))

    monkeypatch.setattr(ScoringSession, "admit", broken)
    return "lost_events"


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered,
                                   _half_left_out])
def test_correct_is_false_with_the_timed_path_broken(tmp_path, monkeypatch,
                                                     fault):
    failing = fault(monkeypatch)
    # what never comes is waited for: keep those waits short here
    monkeypatch.setattr(run, "WARM_BEAT_S", 0.3)
    monkeypatch.setattr(run, "DRAIN_STALL_S", 1.0)
    result, _ = tiny_run(tmp_path, "stream-512k.steady")
    assert result["correct"] is False
    check = result["checks"][failing]
    assert check["value"] > check["limit"], result["checks"]
