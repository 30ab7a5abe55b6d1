"""The Ouro-2.6B configuration and its cell: the configuration's file
against the published config.json's numbers (its top level and its
`model_config` to each other, the program's defaults to the whole of
it), the cut's bytes and the counts from the equations, the two metrics
this configuration brought, and the cell run from its files alone, tiny,
on the CPU: `correct` true as it stands, false with a fault planted under
the timed path (every pass reading pass 1's context, the norm between
passes left out, one pass fewer, a sandwich's second norm left out).
"""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import models, run
from benchmarks.readers import kernel_bytes_roofline, scaled_ratio, trace
# the compile cache placed from outside and one settle thread, as every
# rehearsal of a cell has them (autouse here too, by its import)
from tests.benchmarks.test_bench_laguna import (  # noqa: F401
    as_the_other_rehearsals,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "ouro-2.6b-pp4", "ouro-2.6b-pp4.steady"
SEED = 2 ** 31 + 53
TINY_FRAMES = 10          # frames a second of the tiny cell on the CPU

FULL = "full_attention"
# config.json of ByteDance/Ouro-2.6B (the catalog's row), whole
PUBLISHED = dict(
    head_dim=128, hidden_act="silu", hidden_size=2048, intermediate_size=5632,
    layer_types=[FULL] * 48, max_position_embeddings=65536,
    max_window_layers=48, model_type="ouro", num_attention_heads=16,
    num_hidden_layers=48, num_key_value_heads=16, rms_norm_eps=1e-06,
    rope_scaling=None, rope_theta=1000000, sliding_window=None,
    tie_word_embeddings=False, total_ut_steps=4, early_exit_threshold=1,
    use_sliding_window=False, vocab_size=49152)
CUT = dict(num_hidden_layers=12, layer_types=[FULL] * 12)
OWN = {"window", "context_positions"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_configuration_is_the_published_one_cut_as_it_says():
    cfg = load("benchmarks", "configs", f"{CONFIG}.json")
    manifest = load("BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    # exactly the two keys: all four passes, every width and the whole
    # vocabulary are as published
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    mc = cfg["model_config"]
    for key, value in {**PUBLISHED, **CUT}.items():
        # the configuration as it is run, at the file's top level, and
        # the same numbers in what the program and the reference are given
        assert cfg[key] == mc[key] == value, key
    assert set(mc) - OWN == set(PUBLISHED)
    assert set(cfg["published"]) == set(CUT)
    assert cfg["published"]["num_hidden_layers"] == 48
    # the program's own defaults are the published config, whole
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.models.ouro import OuroConfig

    whole = OuroConfig()
    for key, value in PUBLISHED.items():
        assert getattr(whole, key) == value, key
    assert "4 pipeline stages of 12 whole layers" in cfg["deployment"]
    assert cfg["state_dtype"] == cfg["compute_dtype"] == "bfloat16"
    assert cfg["guarantees"] == load("benchmarks", "configs",
                                     "lfm2-24b-a2b-pp5.json")["guarantees"]
    assert set(cfg["limits"]) == set(load(
        "benchmarks", "configs", "lfm2-24b-a2b-pp5.json")["limits"])
    assert all(cfg["limits"][k] == 0 for k in cfg["limits"]
               if not k.startswith("score_gap"))
    assert set(cfg["limits_why"]) >= {"score_gap_mean", "score_gap_max"}
    # the program takes the file's `model_config` as it stands
    model = build_model(cfg["model"], **mc)
    assert (model.layers, model.passes, model.slots) == (12, 4, 48)
    assert model.at_rest == ("k", "v") and model.windows == {"k": "pos",
                                                             "v": "pos"}
    # a run's contexts start at the window and never fill: no reseed
    traffic = load("benchmarks", "traffic", f"{CELL}.json")
    assert set(traffic) == set(load("benchmarks", "traffic",
                                    "laguna-s-2.1-ep8.steady.json"))
    slices = cfg["devices_per_tenant"] // cfg["frame_devices"]
    seconds = manifest["run_seconds"]
    ticks = -(-(traffic["warm_beats"]
                + seconds * traffic["frames_per_s"]) // slices)
    assert slices == 4 and traffic["warm_beats"] == 3
    assert (cfg["devices_per_tenant"], cfg["frame_devices"]) == (64, 16)
    assert mc["window"] <= cfg["history_ticks"]
    assert mc["window"] + ticks < mc["context_positions"]
    # the cell and what it reports: every metric Laguna's cell reports
    # but the two of a wrapping window and the five of held experts, and
    # the two this configuration brought
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG,
                                                               "steady", 1)
    every = manifest["end_to_end"] + manifest["per_layer"]
    cells = [w["name"] for w in manifest["workloads"]]
    lagunas = {e["name"] for e in every
               if "laguna-s-2.1-ep8.steady" in e.get("workloads", cells)}
    mine = {e["name"] for e in every if CELL in e.get("workloads", cells)}
    assert mine == lagunas - {
        "window_positions_p50", "window_wrapped_rows_per_step",
        "expert_tokens_per_step", "expert_max_tokens_p50",
        "expert_one_tile_runs_per_step", "expert_weight_mb_per_step",
        "expert_tiles_roofline"} | {"loop_weight_mb_per_step",
                                    "context_rows_roofline"}
    assert {"step_roofline", "step_mfu", "latency_p50_ms",
            "context_at_rest_rows_per_step"} <= mine


def test_the_cuts_bytes_as_reckoned():
    """ISSUE 41's arithmetic, from the program's own shapes: a layer
    51,388,416 parameters (102.78 MB), stage 0 817,989,632 (1.636 GB); a
    device's 48 contexts 393,216 B a position, 11.45 GB over the 65 rows
    of a 64-device ring at 448 positions."""
    import jax

    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.ops import context_kernel

    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    model = build_model("ouro-stream", **mc)

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert count(shapes["layers"]) == 12 * 51_388_416
    assert count(shapes["embed"]) == count(shapes["head"]) == 100_663_296
    assert count(shapes) == 817_989_632
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert round(weights / 1e9, 3) == 1.636
    assert model._loop_bytes == 4 * 12 * 2 * 51_388_416 == 4_933_287_936
    one = jax.eval_shape(lambda: model.init_state(1))
    assert one["k"].shape == one["v"].shape == (1, 448, 48 * 2048)
    per_position = 2 * 48 * 2048 * 2
    assert per_position == 393_216
    state = jax.eval_shape(lambda: model.init_state(65))
    table = sum(x.size * x.dtype.itemsize for x in state.values())
    assert round(table / 1e9, 2) == 11.45
    # seeding takes 4 rows a call: their tables are 0.70 GB
    assert model.seed_rows == 4
    # the kernel takes a 2,048-lane block of the 98,304-lane row
    assert context_kernel.fits((65, 448, 98304), "bfloat16", 16, 16, 2048)
    assert 9.6e6 < context_kernel.vmem_bytes((65, 448, 98304), 16, 16,
                                             2048) < 9.8e6
    assert not context_kernel.fits((65, 448, 98304), "bfloat16", 16, 16)
    # the reference's weights are laid out as the program's checkpoint
    counts = models.load("ouro-stream")

    def dims(tree):
        return {k: dims(v) if isinstance(v, dict) else tuple(v[0])
                for k, v in tree.items()}

    assert dims(counts.param_shapes(mc)) == dims(model.param_shapes())
    assert counts.layer_params(mc) == 51_388_416
    assert counts.loop_weight_bytes(mc) == model._loop_bytes


def test_counts_from_the_equations():
    counts = models.load("ouro-stream")
    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    h, runs = 2048, 48
    matrices = 4 * h * h + 3 * h * 5632
    # a context attends to 65..448 positions over a run from a seeded
    # window to a full context
    assert counts._mean_positions(mc) == 256.5
    flops = counts.flops_per_event(mc)
    assert flops == 2 * runs * matrices + 2 * h * 49152 \
        + runs * 4 * h * 256.5
    assert 5.2e9 < flops < 5.3e9          # ISSUE: 82 GFLOP a step of 16
    # (of which 16 x 5.13 in the products)
    per_event = counts.bytes_per_event(mc, "float32")
    assert per_event == ((4_933_287_936 + 2 * h * 49152) / 16 + 2 * h
                         + runs * 2 * 2 * h * 256.5 + 4 * h + 8 + 4)
    peaks = load("benchmarks", "peaks.json")["TPU v5 lite"]
    least, bound = trace.least_seconds(16, flops, per_event, peaks)
    # the weights a step streams four times, and the contexts to their
    # attended length: bound by bytes
    assert bound == "bytes" and 0.0075 < least < 0.0085
    assert 16 * flops / peaks["bf16_flops_per_s"] < least / 10


def test_the_two_metrics_this_configuration_brought_read_their_counter():
    """`loop_weight_mb_per_step` is the counter over the dispatches in
    MB; `context_rows_roofline` the attended bytes over the peak over the
    time the trace gives the operations named `context_rows`, and nothing
    without a trace, without the counter, without such an operation, or
    where the list of operations may have cut one off."""
    manifest = load("BENCHMARK.json")
    specs = {name: load("benchmarks", "metrics", f"{name}.json")
             for name in ("loop_weight_mb_per_step", "context_rows_roofline")}
    # looked up by name: a later PR appends its own entries after these
    entries = {e["name"]: e for e in manifest["per_layer"]}
    for name in specs:
        entry = entries[name]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"]) == ("model and kernel",
                                                    "latency_p50_ms")
    assert [entries["context_rows_roofline"][k]
            for k in ("unit", "better", "source")] == ["%", "higher",
                                                       "device_trace"]
    obs = {"window_metrics": {"counters": {
        "scoring.loop.weight_bytes": 400 * 4_933_287_936.0,
        "scoring.ctx.attended_bytes": 400 * 1.6e9,
        "scoring.dispatches": 400.0}}, "trace": None,
        "peaks": load("benchmarks", "peaks.json")["TPU v5 lite"]}
    mb = scaled_ratio.read(obs, **specs["loop_weight_mb_per_step"]["args"])
    assert round(mb, 1) == 4933.3

    def share():
        return kernel_bytes_roofline.read(
            obs, **specs["context_rows_roofline"]["args"])

    assert specs["context_rows_roofline"]["reader"] == "kernel_bytes_roofline"
    assert share() is None                              # no trace
    calls = [["%context_rows.7 = f32[16,16,128]{2,1,0} custom-call(",
              100 * 0.0040]]
    others = [[f"%fusion.{i} = f32[16,5632] fusion(", 0.05] for i in range(3)]
    obs["trace"] = {"steps": 100, "busy_s": 2.0, "window_s": 3.0,
                    "breakdown": {"device_ops": calls + others}}
    # 1.6 GB over 819 GB/s is 1.95 ms; the 48 calls of a step 4.0 ms
    assert share() == pytest.approx(100 * 1.6e9 / 819e9 / 0.0040, rel=1e-3)
    obs["trace"]["breakdown"]["device_ops"] = others
    assert share() is None                  # the plain path names none
    obs["trace"]["breakdown"]["device_ops"] = calls + others
    obs["window_metrics"]["counters"].pop("scoring.ctx.attended_bytes")
    assert share() is None                  # a program without the counter
    obs["window_metrics"]["counters"].pop("scoring.loop.weight_bytes")
    assert scaled_ratio.read(
        obs, **specs["loop_weight_mb_per_step"]["args"]) is None


def tiny_tree(dst):
    """The benchmark's data with this configuration cut to a size the
    CPU holds: 64 devices behind 4 gateways of 16, hidden 256, 2 heads
    of 128 on 2, an MLP of 512, 2 layers run 3 passes, vocabulary 512,
    contexts of 64 positions. The gap limits are a size's own: the
    file's are the chip's at the published widths, these are this
    size's (as it stands it reads about 0.0006 and 0.00006: the
    program's products are the reference's, operand for operand; the
    four faults read 0.004 and more on the mean)."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    path = dst / "benchmarks" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["model_config"].update(
        hidden_size=256, intermediate_size=512, num_attention_heads=2,
        num_key_value_heads=2, vocab_size=512, num_hidden_layers=2,
        layer_types=[FULL] * 2, total_ut_steps=3, window=16,
        context_positions=64)
    cfg.update(history_ticks=20, anomaly_rate=0.02, threshold=7.5)
    cfg["limits"].update(score_gap_max=0.004, score_gap_mean=0.0004)
    path.write_text(json.dumps(cfg))
    # a rate the CPU holds: a loaded test machine's step is not the
    # chip's, and a late frame would fail the run's own counts
    path = dst / "benchmarks" / "traffic" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "frames_per_s": TINY_FRAMES}))
    return str(dst)


def _as_it_stands(monkeypatch):
    return None


def _none_is_no_norm(monkeypatch):
    """`rms(x, None)` is `x`: a norm whose weight a fault took away is
    left out."""
    import jax.numpy as jnp

    from sitewhere_tpu.models import ouro

    real = ouro.rms
    monkeypatch.setattr(ouro, "rms", lambda x, w, eps: x.astype(jnp.float32)
                        if w is None else real(x, w, eps))


def _every_pass_reads_pass_ones_context(monkeypatch):
    """The contexts shared across passes: a layer reads the block of its
    first pass in every pass (seeding fills all of them, and each pass's
    entry is still written to its own)."""
    from sitewhere_tpu.models.ouro import OuroStreamModel

    real = OuroStreamModel._attend

    def shared(self, *args):
        *args, slot = args
        return real(self, *args, slot % self.layers)

    monkeypatch.setattr(OuroStreamModel, "_attend", shared)
    return "score_gap_mean"


def _the_norm_between_passes_left_out(monkeypatch):
    """A pass feeds the next its residual stream as it is; the last one
    is still normed before the head."""
    from sitewhere_tpu.models.ouro import OuroStreamModel

    _none_is_no_norm(monkeypatch)
    real = OuroStreamModel._loop
    monkeypatch.setattr(
        OuroStreamModel, "_loop", lambda self, params, *args: real(
            self, {**params, "norm": None}, *args))
    return "score_gap_mean"


def _one_pass_fewer(monkeypatch):
    from sitewhere_tpu.models.ouro import OuroStreamModel

    real = OuroStreamModel.__init__

    def init(self, cfg):
        real(self, cfg)
        self.passes -= 1

    monkeypatch.setattr(OuroStreamModel, "__init__", init)
    return "score_gap_mean"


def _a_sandwichs_second_norm_left_out(monkeypatch):
    """The MLP's output added to the stream as it comes."""
    from sitewhere_tpu.models.ouro import OuroStreamModel

    _none_is_no_norm(monkeypatch)
    real = OuroStreamModel._layer
    monkeypatch.setattr(
        OuroStreamModel, "_layer", lambda self, p, *args: real(
            self, {**p, "mlp_out_norm": None}, *args))
    return "score_gap_mean"


@pytest.mark.parametrize("fault", [
    _as_it_stands, _every_pass_reads_pass_ones_context,
    _the_norm_between_passes_left_out, _one_pass_fewer,
    _a_sandwichs_second_norm_left_out])
def test_cell_tiny_on_cpu_from_its_files_alone(tmp_path, monkeypatch, fault):
    failing = fault(monkeypatch)
    result, info = run.run_cell(CELL, SEED, 1.0, True, "cpu",
                                root=tiny_tree(tmp_path))
    frames = TINY_FRAMES
    assert info["frames"] == frames and info["rejected_events"] == 0
    assert result["attempted"] == frames * 16 and result["failed"] == 0
    # every served score is compared, the warm-up beats' too
    assert info["compared_events"] == (3 + frames) * 16
    if failing is None:
        assert result["correct"], result["checks"]
        # what the counters give is in a traced run's line; a CPU trace
        # has no device plane, so the trace's readers leave theirs out
        got = {k: v["value"] for k, v in result["metrics"].items()}
        assert {"context_positions_p50", "events_per_dispatch",
                "loop_weight_mb_per_step", "context_at_rest_rows_per_step",
                "merge_fast_per_batch", "ring_ascending_per_dispatch"} \
            <= set(got)
        assert not {"step_mfu", "context_rows_roofline",
                    "expert_tiles_roofline"} & set(got)
        assert 16 <= got["context_positions_p50"] <= 24
        # no ratio of two of a one-second window's counts is held to a
        # number here (PERF.md section 7 on test_bench_dsv3.py): the
        # counters' arithmetic is tests/test_ouro.py's
        assert got["loop_weight_mb_per_step"] > 0
        assert got["context_at_rest_rows_per_step"] == 0   # the CPU gathers
    else:
        assert result["correct"] is False
        check = result["checks"][failing]
        assert check["value"] > check["limit"], result["checks"]


def test_control_stands_clear_of_the_configurations_precision():
    """The reference one precision down against the reference in the
    configuration's: at a small size its mean gap is several times what
    bfloat16 itself stands from float32, which is the room a limit needs
    between the program and the control. (The limits in the file are the
    chip's, at the published widths: the control's readings there are in
    PERF.md, section 2.)"""
    from benchmarks import compare, gen

    cfg = load("benchmarks", "configs", f"{CONFIG}.json")
    reference = models.load(cfg["model"])
    mc = json.loads(json.dumps(cfg["model_config"]))
    mc.update(hidden_size=256, intermediate_size=512, num_attention_heads=2,
              num_key_value_heads=2, vocab_size=512, num_hidden_layers=2,
              layer_types=[FULL] * 2, window=24, context_positions=48)
    fleet = gen.Fleet(5, 0, 24, 0.02, 12.0)
    hist = np.stack([fleet.values(k, spikes=False) for k in range(28)], 1)
    frames = np.stack([fleet.values(28 + k) for k in range(12)])
    fed = np.ones(frames.shape, bool)
    params = reference.tenant_params(5, 0, mc)

    def scores(dtype):
        return reference.run(params, hist, frames, fed, mc, dtype)

    stated = scores(cfg["compute_dtype"])
    _, own = compare.score_gaps(stated, scores("float32"))
    _, control = compare.score_gaps(
        scores(compare.LOWER[cfg["compute_dtype"]]), stated)
    assert control > 5 * own > 0
