"""The benchmark's data and yardstick, checked without a chip: the
manifest against the contract's limits, every file it names, the counts,
the trace reduction on a small recorded trace, the histogram reader, and
the plain reference against the program's model and against its control.
"""

import json
import os
import re

import numpy as np
import pytest

from benchmarks import compare, gen, models, xplane
from benchmarks.readers import histogram_quantile, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def manifest():
    return load(ROOT, "BENCHMARK.json")


def line(text, limit=200):
    return (1 <= len(text) <= limit and text.isascii() and text.isprintable())


def test_manifest_keeps_the_contracts_limits(manifest):
    m = manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert all(line(w) for w in m["command"]) and len(m["command"]) <= 32
    assert 1 <= len(m["paths"]) <= 16
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4) and line(w["why"])
    cells = [w["name"] for w in m["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(cells)
    assert {c["name"] for c in m["configs"]} == {w["config"] for w in m["workloads"]}
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(cells) // 4)
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert e["source"] in SOURCES and line(e["layer"])
    every = m["end_to_end"] + m["per_layer"]
    assert len({e["name"] for e in every}) == len(every)
    for e in every:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert set(e.get("workloads", cells)) <= set(cells)
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}


def test_every_cell_reports_what_its_layer_metrics_move(manifest):
    m = manifest
    cells = [w["name"] for w in m["workloads"]]
    e2e = {e["name"]: set(e.get("workloads", cells)) for e in m["end_to_end"]}
    for cell in cells:
        assert cell in e2e["setup_s"]
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in e.get("workloads", e2e[e["moves"]])
                   for e in m["per_layer"])
    for e in m["per_layer"]:
        assert e["moves"] in e2e and e["moves"] != "setup_s"
        # each listed cell reports the end-to-end metric this one moves
        assert set(e.get("workloads", e2e[e["moves"]])) <= e2e[e["moves"]]
        if e["name"].endswith("_roofline") or "mfu" in e["name"]:
            assert e["unit"] == "%"
    # the harness reads the same rule: a per-layer metric with no
    # `workloads` key is reported wherever the metric it moves is
    from benchmarks.run import Cell

    for cell in cells:
        want = {e["name"] for e in m["per_layer"]
                if cell in e.get("workloads", e2e[e["moves"]])}
        assert {e["name"] for e in Cell(ROOT, cell).metrics("per_layer")} == want


def test_every_file_the_manifest_names_is_there_and_parses(manifest):
    m = manifest
    for c in m["configs"]:
        cfg = load(ROOT, c["file"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["name"] == c["name"]
        assert cfg["compute_dtype"] in compare.LOWER
        assert models.load(cfg["model"]).run
        assert set(cfg["limits"]) >= {"score_gap_max", "lost_events"}
        assert cfg["guarantees"]
    for w in m["workloads"]:
        traffic = load(BENCH, "traffic", f"{w['name']}.json")
        assert traffic["loop"] in ("open", "closed")
        key = "frames_per_s" if traffic["loop"] == "open" else "inflight_frames"
        assert traffic[key] > 0
        cfg = load(ROOT, {c["name"]: c for c in m["configs"]}[
            w["config"]]["file"])
        assert cfg["chips"] == w["chips"]
        assert cfg["devices_per_tenant"] % cfg.get(
            "frame_devices", cfg["devices_per_tenant"]) == 0
    for e in m["per_layer"]:
        spec = load(BENCH, "metrics", f"{e['name']}.json")
        for key in ("unit", "layer", "moves"):
            assert spec[key] == e[key]
        # which cells report it is the manifest's alone to say: a later
        # PR lists its cell there and edits no file
        assert "workloads" not in spec
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    peaks = load(BENCH, "peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_files_under_paths_are_named_from_a_names_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(base, f), ROOT))


def test_counts_at_hidden_64():
    counts = models.load("lstm-stream")
    mc = {"hidden": 64, "layers": 1, "window": 64}
    assert counts.flops_per_event(mc) == 33408
    assert counts.state_row_bytes(mc) == 528
    per_event = counts.bytes_per_event(mc, "float16")
    assert per_event == 2 * 528 + 8 + 2
    peaks = load(BENCH, "peaks.json")["TPU v5 lite"]
    least, bound = trace.least_seconds(16384, 33408, per_event, peaks)
    assert bound == "bytes"
    assert least == pytest.approx(16384 * per_event / 819e9)


def test_trace_reduction_on_a_recorded_trace():
    planes = load(os.path.dirname(__file__), "data", "trace_small.json")
    # two overlapping operations count once; the gap between is idle
    assert xplane.merge([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    out = xplane.reduce(planes)
    ops = next(l["events"] for p in planes for l in p["lines"]
               if p["name"] == "/device:TPU:0" and l["name"] == "XLA Ops")
    assert 0 < out["busy_s"] <= sum(d for _, _, d in ops) * 1e-9 + 1e-12
    assert out["busy_s"] <= out["span_s"]
    assert out["steps"] >= 1 and 0 < out["step_s"] <= out["span_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert all(name == "unattributed"
               for name, _ in out["breakdown"]["idle_gaps"])
    idle = 1.0 - out["busy_s"] / out["span_s"]
    assert 0.0 <= idle < 1.0
    # a trace with no operation on a device plane gives nothing, not 0
    assert xplane.reduce([{"name": "/host:CPU", "lines": []}]) is None


def test_histogram_quantile_reads_the_window_only():
    obs = {"window_metrics": {"histograms": {"h": {
        "buckets": [0.001, 0.002, 0.004], "counts": [0, 10, 10, 0],
        "max": 0.0035}}}}
    assert histogram_quantile.read(obs, "h", 0.5, 1000.0) == pytest.approx(2.0)
    assert histogram_quantile.read(obs, "h", 0.75, 1000.0) == pytest.approx(3.0)
    assert histogram_quantile.read(obs, "missing", 0.5) is None
    obs["window_metrics"]["histograms"]["h"]["counts"] = [0, 0, 0, 0]
    assert histogram_quantile.read(obs, "h", 0.5) is None


def test_memory_is_arrays_and_scratch_read_at_one_instant(monkeypatch):
    """The chip's share is what the runtime holds for arrays plus the
    scratch it keeps for compiled programs, on the fullest device at the
    fullest instant; a backend that reports nothing reads 0."""
    import jax

    from benchmarks.run import MemoryWatch

    class Chip:
        def __init__(self, readings):
            self.readings = iter(readings)

        def memory_stats(self):
            return next(self.readings)

    def st(in_use, reserved, peak):
        return {"bytes_in_use": in_use, "bytes_reserved": reserved,
                "peak_bytes_in_use": peak, "peak_bytes_reserved": reserved}

    chips = [Chip([st(100, 0, 100), st(640, 270, 640), st(150, 4830, 640)]),
             Chip([st(50, 0, 50), st(60, 10, 60), None])]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    watch = MemoryWatch()
    assert watch.report()["memory_peak_bytes"] == 0
    for _ in range(3):
        watch.sample()
    assert watch.report() == {"memory_peak_bytes": 4980,
                              "memory_arrays_peak_bytes": 640,
                              "memory_scratch_peak_bytes": 4830}


def test_generator_is_reproducible_and_wire_compatible():
    from sitewhere_tpu.domain.batch import BatchContext, MeasurementBatch

    a, b = gen.Fleet(2 ** 31 + 9, 3, 50, 0.1, 12.0), gen.Fleet(2 ** 31 + 9, 3, 50, 0.1, 12.0)
    assert (a.values(70) == b.values(70)).all()
    assert (a.values(70) != a.values(71)).any()
    frame = a.frame(70)
    assert int.from_bytes(frame[:4], "little") == len(frame) - 4
    batch = MeasurementBatch.decode(frame[4:], BatchContext(tenant_id="t"))
    assert (batch.value == a.values(70)).all()
    assert (batch.device_index == np.arange(50)).all()
    assert (gen.tick_of(batch.ts) == 70).all()
    # a fleet behind five gateways: frame 352 is slice 2 of tick 70
    g = gen.Fleet(2 ** 31 + 9, 3, 50, 0.1, 12.0, frame_devices=10)
    part = MeasurementBatch.decode(g.frame(70 * 5 + 2)[4:],
                                   BatchContext(tenant_id="t"))
    assert (part.device_index == np.arange(20, 30)).all()
    assert (part.value == a.values(70)[20:30]).all()
    assert (gen.tick_of(part.ts) == 70).all()
    with pytest.raises(ValueError):
        gen.Fleet(1, 0, 50, frame_devices=16)


@pytest.mark.parametrize("history", [68, 0])
def test_reference_agrees_with_the_model_and_its_control_does_not(
        history, monkeypatch):
    """The plain reference against `StreamingLstmModel.step_score` through
    the program's own jitted step, from a seeded fleet and from a cold
    one; then the control (the reference one precision down, in the
    program's place) against the limits."""
    import jax
    import jax.numpy as jnp

    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.scoring.stream import streaming_step

    cfg = load(BENCH, "configs", "stream-512k.json")
    reference = models.load(cfg["model"])
    mc = {"window": 64, "hidden": 64, "layers": 1}
    d, w, h, f = 96, 64, 64, 24
    fleet = gen.Fleet(11, 0, d, 0.02, 12.0)
    hist = np.empty((d, history), np.float32)
    for k in range(history):
        hist[:, k] = fleet.values(k, spikes=False)
    frames = np.stack([fleet.values(history + k) for k in range(f)])
    params = reference.init_params(11, h)
    fed = np.ones(frames.shape, bool)
    ref = reference.run(params, hist, frames, fed, mc, "bfloat16", block=8)
    model = build_model("lstm-stream", window=w, hidden=h)
    if history:
        state = jax.jit(model.warm_state)(params, jnp.asarray(hist[:, -w:]),
                                          jnp.ones((d, w), bool))
    else:
        state = model.init_state(d)
    step, served = jax.jit(streaming_step(model, jnp.float16)), []
    for k in range(f):
        state, s = step(params, state, jnp.arange(d), jnp.asarray(frames[k]))
        served.append(np.asarray(s, np.float32))
    served = np.stack(served)
    assert (ref > cfg["threshold"]).any()

    def judged(scores):
        g_max, g_mean = compare.score_gaps(scores, ref)
        numbers = {"score_gap_max": g_max, "score_gap_mean": g_mean}
        limits = {k: cfg["limits"][k] for k in numbers}
        return compare.verdict(numbers, limits)

    ok, checks = judged(served)
    assert ok, checks
    control = reference.run(params, hist, frames, fed, mc,
                            compare.LOWER[cfg["compute_dtype"]], block=8)
    ok, checks = judged(control)
    assert not ok, checks
    assert checks["score_gap_mean"]["value"] > 3 * cfg["limits"]["score_gap_mean"]
    # seeded in blocks of rows it gives what it gives seeded in one call
    monkeypatch.setattr(reference, "SEED_ROWS", 32)
    assert (reference.run(params, hist, frames, fed, mc, "bfloat16", block=8)
            == ref).all()
    # a device keeps its state through a tick it was not fed
    # (tick 12: a cold fleet's first eight scores are gated to 0)
    fed[12, :48] = False
    skipped = reference.run(params, hist, frames, fed, mc, "bfloat16", block=8)
    assert (skipped[:12] == ref[:12]).all() and (skipped[13:, 48:] == ref[13:, 48:]).all()
    assert (skipped[13, :48] != ref[13, :48]).any()
