"""The LFM2-24B-A2B configuration and its cell: the configuration's file
against the published config.json's numbers, the cut's bytes and the
counts from the equations, the two metrics this configuration brought,
and the cell run from its files alone, tiny, on the CPU: `correct` true
as it stands, false with a fault planted under the timed path (a conv
that forgets `s_{t-2}`, the gate `C` left out, the per-head norms left
out, a step that returns the conv leaves unchanged).
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
CONFIG, CELL = "lfm2-24b-a2b-pp5", "lfm2-24b-a2b-pp5.steady"
SEED = 2 ** 31 + 47
TINY_FRAMES = 10          # frames a second of the tiny cell on the CPU

CONV, FULL = "conv", "full_attention"
# config.json of LiquidAI/LFM2-24B-A2B (the catalog's row), whole
PUBLISHED = dict(
    model_type="lfm2_moe", vocab_size=65536, hidden_size=2048,
    intermediate_size=11776, num_hidden_layers=40, num_attention_heads=32,
    num_key_value_heads=8, max_position_embeddings=128000, norm_eps=1e-05,
    conv_L_cache=3, conv_bias=False, num_dense_layers=2, num_experts=64,
    num_experts_per_tok=4, moe_intermediate_size=1536, use_expert_bias=True,
    norm_topk_prob=True, routed_scaling_factor=1,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    layer_types=[CONV, CONV, FULL] + [CONV, CONV, CONV, FULL] * 9 + [CONV])
CUT = dict(num_hidden_layers=8, layer_types=PUBLISHED["layer_types"][:8])
OWN = {"tie_embedding", "window", "context_positions"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_configuration_is_the_published_one_cut_as_it_says():
    cfg = load("benchmarks", "configs", f"{CONFIG}.json")
    manifest = load("BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    # exactly the two keys: the experts (64 of 64), the vocabulary and
    # both dense layers are as published
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    mc = cfg["model_config"]
    for key, value in {**PUBLISHED, **CUT}.items():
        # the configuration as it is run, at the file's top level, and
        # the same numbers in what the program and the reference are given
        assert cfg[key] == mc[key] == value, key
    assert set(mc) - set(cfg) == {"window", "context_positions"}
    assert set(mc) - OWN == set(PUBLISHED)
    assert cfg["tie_embedding"] is mc["tie_embedding"] is True
    assert set(cfg["published"]) == set(CUT)
    assert cfg["published"]["num_hidden_layers"] == 40
    # the program's own defaults are the published config, whole: eight
    # layers of it are the file's eight
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.models.lfm2 import Lfm2Config

    whole = Lfm2Config()
    for key, value in PUBLISHED.items():
        assert getattr(whole, key) == value, key
    assert "5 stages of 8 layers" in cfg["deployment"]
    assert cfg["state_dtype"] == cfg["compute_dtype"] == "bfloat16"
    assert cfg["guarantees"] == load("benchmarks", "configs",
                                     "laguna-s-2.1-ep8.json")["guarantees"] \
        == load("benchmarks", "configs", "deepseek-v3-ep16.json")["guarantees"] \
        == load("benchmarks", "configs",
                "olmo-hybrid-7b-pp8.json")["guarantees"]
    assert set(cfg["limits"]) == {
        "score_gap_max", "score_gap_mean", "alert_mismatches", "lost_events",
        "duplicate_events", "reordered_events", "alerts_not_emitted",
        "alerts_not_stored", "failed_health", "compiles_in_window"}
    assert all(cfg["limits"][k] == 0 for k in cfg["limits"]
               if not k.startswith("score_gap"))
    assert set(cfg["limits_why"]) >= {"score_gap_mean", "score_gap_max"}
    # the program takes the file's `model_config` as it stands
    model = build_model(cfg["model"], **mc)
    assert model.layers == 8 and model.kinds == CUT["layer_types"]
    assert sorted(model.windows) == ["k2", "k6", "v2", "v6"]
    assert model.dense == [True, True] + [False] * 6
    assert (model.experts.held, model.experts.first) == (64, 0)
    # a run's contexts start past the window and never fill: no reseed
    traffic = load("benchmarks", "traffic", f"{CELL}.json")
    assert set(traffic) == set(load("benchmarks", "traffic",
                                    "laguna-s-2.1-ep8.steady.json"))
    slices = cfg["devices_per_tenant"] // cfg["frame_devices"]
    seconds = manifest["run_seconds"]
    ticks = -(-(traffic["warm_beats"]
                + seconds * traffic["frames_per_s"]) // slices)
    assert slices == 5 and traffic["warm_beats"] == 3
    assert (cfg["devices_per_tenant"], cfg["frame_devices"]) == (2560, 512)
    assert mc["window"] <= cfg["history_ticks"]
    assert mc["window"] + ticks <= mc["context_positions"]
    # the cell and what it reports: every metric Laguna's cell reports
    # but the two of a wrapping window, and the bytes a step rewrites
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG,
                                                               "steady", 1)
    every = manifest["end_to_end"] + manifest["per_layer"]
    cells = [w["name"] for w in manifest["workloads"]]
    lagunas = {e["name"] for e in every
               if "laguna-s-2.1-ep8.steady" in e.get("workloads", cells)}
    mine = {e["name"] for e in every if CELL in e.get("workloads", cells)}
    assert mine == lagunas - {"window_positions_p50",
                              "window_wrapped_rows_per_step"} \
        | {"state_rewritten_mb_per_step"}
    assert {"expert_weight_mb_per_step", "expert_tiles_roofline",
            "step_roofline", "step_mfu", "latency_p50_ms"} <= mine


def test_the_cuts_bytes_as_reckoned():
    """ISSUE 39's arithmetic, from the program's own shapes: a conv
    operator 16,783,360 parameters, an attention operator 10,485,888, a
    dense MLP 72,351,744, an expert 9,437,184 and a layer of 64 with
    router and bias 604,110,912; 4,025,293,440 in all, 8.05 GB; a device
    2,150,416 B, 5.51 GB over the 2,561 rows of a 2,560-device ring."""
    import jax

    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.ops import context_kernel, expert_kernel

    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    model = build_model("lfm2-stream", **mc)

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    conv = {k: shapes["layer0"][k] for k in ("in", "conv", "out")}
    attention = {k: shapes["layer2"][k]
                 for k in ("q", "k", "v", "o", "q_norm", "k_norm")}
    assert count(conv) == 16_783_360 and count(attention) == 10_485_888
    assert count(shapes["layer0"]["mlp"]) == 72_351_744
    assert count(shapes["layer2"]["experts"]["e0"]) == 9_437_184
    assert count([shapes["layer2"]["experts"],
                  shapes["layer2"]["router"]]) == 604_110_912
    assert len(shapes["layer7"]["experts"]) == 64
    assert "shared" not in shapes["layer2"] and "head" not in shapes
    assert count(shapes["embed"]) == 134_217_728
    assert count(shapes) == 4_025_293_440
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert round(weights / 1e9, 2) == 8.05
    one = jax.eval_shape(lambda: model.init_state(1))
    row = {name: x.size * x.dtype.itemsize for name, x in one.items()}
    assert row["k2"] == row["v6"] == 524_288          # exactly 512 KiB
    assert row["c0"] == 8_192 and one["c0"].shape == (1, 32, 128)
    assert sum(row.values()) == 4 * 524_288 + 6 * 8_192 + 4_112 == 2_150_416
    state = jax.eval_shape(lambda: model.init_state(2561))
    table = sum(x.size * x.dtype.itemsize for x in state.values())
    assert round(table / 1e9, 2) == 5.51
    # which kernels take these shapes: the experts' does, attention's
    # does not (a key-value head is half a lane tile)
    assert expert_kernel.fits(512, 2048, 1536, 128)
    assert expert_kernel.vmem_bytes(512, 2048, 128) == 11_534_336
    assert not context_kernel.fits((2561, 512, 512), "bfloat16", 32, 8)
    # the reference's weights are laid out as the program's checkpoint
    counts = models.load("lfm2-stream")

    def dims(tree):
        return {k: dims(v) if isinstance(v, dict) else tuple(v[0])
                for k, v in tree.items()}

    assert dims(counts.param_shapes(mc)) == dims(model.param_shapes())
    assert counts.state_row_bytes(mc) == 6 * 8_192
    assert counts.expert_leaf_bytes(mc) == 7_247_757_312


def test_counts_from_the_equations():
    counts = models.load("lfm2-stream")
    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    h = 2048
    conv, attention = 4 * h * h, 2 * h * h + 2 * h * 512
    dense, expert, router = 3 * h * 11776, 3 * h * 1536, h * 64
    head = h * 65536
    resident, touched = counts._matrix_params(mc)
    assert resident == 6 * conv + 2 * attention + 2 * dense \
        + 6 * (router + 64 * expert) + head
    # the ACTIVE parameters: 4 of 64 experts a token, every one held
    assert touched == 6 * conv + 2 * attention + 2 * dense \
        + 6 * (router + 4 * expert) + head
    assert 625e6 < touched < 640e6                # ISSUE: 635M
    # an attention layer attends to 97..512 positions over a run from a
    # seeded window to a full context
    assert counts._mean_positions(mc) == 304.5
    flops = counts.flops_per_event(mc)
    assert flops == 2 * touched + 6 * 8 * h + 2 * 4 * h * 304.5
    assert 1.25e9 < flops < 1.29e9                # ISSUE: 1.27 GFLOP
    per_event = counts.bytes_per_event(mc, "float32")
    # every held expert's leaves once a frame, the conv states once read
    # and once written, whatever the program does
    assert per_event == (2 * resident / 512 + 2 * 6 * 8192
                         + 2 * 2048 * 304.5 + 4 * h + 8 + 4)
    assert 16.5e6 < per_event < 17.2e6            # ISSUE: 16.6 MB at 200
    assert 2 * resident > counts.expert_leaf_bytes(mc) == 6 * 64 * 2 * expert
    peaks = load("benchmarks", "peaks.json")["TPU v5 lite"]
    least, bound = trace.least_seconds(512, flops, per_event, peaks)
    assert bound == "bytes" and 0.0100 < least < 0.0108   # ISSUE: 10.3 ms
    assert 512 * flops / peaks["bf16_flops_per_s"] < least / 3


def test_the_two_metrics_this_configuration_brought_read_their_counter():
    """`expert_weight_mb_per_step` is the counter over the dispatches in
    MB; `expert_tiles_roofline` those bytes over the peak over the time
    the trace gives the operations named `expert_tiles`, and nothing
    without a trace, without the counter, without such an operation, or
    where the list of operations may have cut one off."""
    manifest = load("BENCHMARK.json")
    specs = {name: load("benchmarks", "metrics", f"{name}.json")
             for name in ("expert_weight_mb_per_step",
                          "expert_tiles_roofline")}
    three = ["deepseek-v3-ep16.steady", "laguna-s-2.1-ep8.steady", CELL]
    for entry in manifest["per_layer"][-2:]:
        assert entry["name"] in specs and entry["workloads"] == three
        assert (entry["layer"], entry["moves"]) == ("model and kernel",
                                                    "latency_p50_ms")
    assert [manifest["per_layer"][-1][k] for k in ("unit", "better", "source")
            ] == ["%", "higher", "device_trace"]
    obs = {"window_metrics": {"counters": {
        "scoring.moe.weight_bytes": 700 * 7_247_757_312.0,
        "scoring.dispatches": 700.0}}, "trace": None,
        "peaks": load("benchmarks", "peaks.json")["TPU v5 lite"]}
    mb = scaled_ratio.read(obs, **specs["expert_weight_mb_per_step"]["args"])
    assert round(mb, 1) == 7247.8

    def share():
        return kernel_bytes_roofline.read(
            obs, **specs["expert_tiles_roofline"]["args"])

    assert specs["expert_tiles_roofline"]["reader"] == "kernel_bytes_roofline"
    assert share() is None                              # no trace
    calls = [[f"%expert_tiles.{12 + i} = f32[512,2048]{{1,0:T(8,128)}} "
              "custom-call(", 100 * 0.0017] for i in range(6)]
    others = [[f"%fusion.{i} = bf16[512,11776] fusion(", 0.05]
              for i in range(3)]
    obs["trace"] = {"steps": 100, "busy_s": 2.0, "window_s": 3.0,
                    "breakdown": {"device_ops": calls + others}}
    # 7.248 GB over 819 GB/s is 8.85 ms; six calls of 1.7 ms are 10.2
    assert share() == pytest.approx(100 * 8.8495 / 10.2, rel=1e-4)
    obs["trace"]["breakdown"]["device_ops"] = others
    assert share() is None                  # the plain path names none
    # ten names, the last of them a call: another may lie under the cut
    obs["trace"]["breakdown"]["device_ops"] = others + [
        [f"%fusion.9{i} = f32[8]", 0.3] for i in range(2)] + calls[:5]
    assert share() is None
    obs["trace"]["breakdown"]["device_ops"] = calls + others
    obs["window_metrics"]["counters"].pop("scoring.moe.weight_bytes")
    assert share() is None                  # a program without the counter
    assert scaled_ratio.read(
        obs, **specs["expert_weight_mb_per_step"]["args"]) is None


def tiny_tree(dst):
    """The benchmark's data with this configuration cut to a size the
    CPU holds: 80 devices behind 5 gateways of 16, hidden 256, 4 query
    heads of 64 on 2, a dense MLP of 256, 8 experts of 64 of which 2 a
    token, vocabulary 64. The gap limits are a size's own: the file's
    are the chip's at the published widths, these are this size's (as it
    stands it reads 0.00026 and 0.0000055: the program's products are the
    reference's, operand for operand; the four faults read 0.069 to 0.33
    and 0.0088 to 0.092)."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    path = dst / "benchmarks" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["model_config"].update(
        hidden_size=256, intermediate_size=256, moe_intermediate_size=64,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=64,
        num_experts=8, num_experts_per_tok=2, window=16,
        context_positions=40)
    cfg.update(devices_per_tenant=80, frame_devices=16, history_ticks=20,
               anomaly_rate=0.02, threshold=4.5)
    cfg["limits"].update(score_gap_max=0.002, score_gap_mean=0.0002)
    path.write_text(json.dumps(cfg))
    # a rate the CPU holds: a loaded test machine's step is not the
    # chip's, and a late frame would fail the run's own counts
    path = dst / "benchmarks" / "traffic" / f"{CELL}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "frames_per_s": TINY_FRAMES}))
    return str(dst)


def _as_it_stands(monkeypatch):
    return None


def _a_conv_that_forgets_its_older_input(monkeypatch):
    """`s_{t-2}` read as 0: the older of the two stored inputs."""
    from sitewhere_tpu.models.lfm2 import Lfm2StreamModel

    class Forgetful:
        def __init__(self, taps):
            self.taps = taps

        def read(self, after):
            rows = self.taps.read(after)
            return rows.at[:, :rows.shape[1] // 2].set(0)

        def write(self, rows, then):
            return self.taps.write(rows, then)

    real = Lfm2StreamModel._conv_decode
    monkeypatch.setattr(
        Lfm2StreamModel, "_conv_decode",
        lambda self, p, x, taps: real(self, p, x, Forgetful(taps)))
    return "score_gap_mean"


def _the_gate_c_left_out(monkeypatch):
    """`y = c_t` where the operator says `C * c_t`."""
    import jax.numpy as jnp

    from sitewhere_tpu.models.lfm2 import Lfm2StreamModel

    real = Lfm2StreamModel._conv_project

    def ungated(self, p, u):
        s, gate = real(self, p, u)
        return s, jnp.ones_like(gate)

    monkeypatch.setattr(Lfm2StreamModel, "_conv_project", ungated)
    return "score_gap_mean"


def _the_per_head_norms_left_out(monkeypatch):
    """Queries and keys turned as they come out of their projections."""
    import jax.numpy as jnp

    from sitewhere_tpu.models import lfm2

    real = lfm2.rms

    def rms(x, w, eps):
        if x.ndim >= 3 and w.shape[-1] != 256:      # a head's 64
            return x.astype(jnp.float32)
        return real(x, w, eps)

    monkeypatch.setattr(lfm2, "rms", rms)
    return "score_gap_mean"


def _a_step_that_returns_the_conv_leaves_unchanged(monkeypatch):
    """The ring step writes back the conv states it read (seeding, one
    convolution over the window with no ring in it, still moves them)."""
    from sitewhere_tpu.scoring import stream

    real = stream.RowsInTurn.write

    def write(self, rows, then):
        return real(self, stream._rows(self.table, self._dev), then)

    monkeypatch.setattr(stream.RowsInTurn, "write", write)
    return "score_gap_mean"


@pytest.mark.parametrize("fault", [
    _as_it_stands, _a_conv_that_forgets_its_older_input,
    _the_gate_c_left_out, _the_per_head_norms_left_out,
    _a_step_that_returns_the_conv_leaves_unchanged])
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
                "expert_tokens_per_step", "expert_max_tokens_p50",
                "expert_one_tile_runs_per_step", "expert_weight_mb_per_step",
                "state_rewritten_mb_per_step",
                "context_at_rest_rows_per_step", "merge_fast_per_batch",
                "ring_ascending_per_dispatch"} <= set(got)
        assert not {"step_mfu", "expert_tiles_roofline",
                    "window_positions_p50", "state_absmax_p50"} & set(got)
        assert 16 <= got["context_positions_p50"] <= 32
        # no ratio of two of a one-second window's counts is held to a
        # number here (PERF.md section 7 on test_bench_dsv3.py): the
        # counters' arithmetic is tests/test_lfm2.py's
        assert got["expert_weight_mb_per_step"] > 0
        assert got["state_rewritten_mb_per_step"] > 0
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
    mc.update(hidden_size=256, intermediate_size=512,
              moe_intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=2, vocab_size=512, num_experts=16,
              window=24, context_positions=48, num_hidden_layers=4,
              layer_types=[CONV, CONV, FULL, CONV], num_dense_layers=1)
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
