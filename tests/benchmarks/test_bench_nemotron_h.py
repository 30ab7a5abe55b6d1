"""The NVIDIA-Nemotron-3-Super-120B-A12B configuration and its cell: the
configuration's file against the published config.json's numbers, the
cut's bytes and the counts from the equations, and the cell run from its
files alone, tiny, on the CPU: `correct` true as it stands, false with a
fault planted under the timed path (the decay left out, the `D` skip
left out, a conv that forgets its oldest tap, `relu` in place of
`relu` squared).
"""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import models, run
from benchmarks.readers import trace
# the compile cache placed from outside and one settle thread, as every
# rehearsal of a cell has them (autouse here too, by its import)
from tests.benchmarks.test_bench_laguna import (  # noqa: F401
    as_the_other_rehearsals,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "nemotron-3-super-ep8", "nemotron-3-super-ep8.steady"
SEED = 2 ** 31 + 46
TINY_FRAMES = 10          # frames a second of the tiny cell on the CPU
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")

# config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (the
# catalog's row), whole
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=4096, hybrid_override_pattern=PATTERN,
    intermediate_size=2688, layer_norm_epsilon=1e-05, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=128, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=2688,
    moe_latent_size=1024, moe_shared_expert_intermediate_size=5376,
    moe_shared_expert_overlap=False, mtp_hybrid_override_pattern="*E",
    n_group=1, n_groups=8, n_routed_experts=512, n_shared_experts=1,
    norm_eps=1e-05, norm_topk_prob=True, num_attention_heads=32,
    num_experts_per_tok=22, num_hidden_layers=88, num_key_value_heads=2,
    num_logits_to_keep=1, num_nextn_predict_layers=1,
    partial_rotary_factor=1, rescale_prenorm_residual=True,
    residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=5,
    sliding_window=None, ssm_state_size=128, tie_word_embeddings=False,
    time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
    topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True, vocab_size=131072)
CUT = dict(num_hidden_layers=11, hybrid_override_pattern="MEMEMEM*EME",
           n_routed_experts_held=64, vocab_held=16384,
           num_nextn_predict_layers=0)
SCORER = {"first_expert", "n_routed_experts_held", "vocab_held", "window",
          "context_positions"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_configuration_is_the_published_one_cut_as_it_says():
    cfg = load("benchmarks", "configs", f"{CONFIG}.json")
    entry = {c["name"]: c for c in load("BENCHMARK.json")["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    mc = cfg["model_config"]
    for key, value in {**PUBLISHED, **CUT}.items():
        # the configuration as it is run, at the file's top level, and
        # the same numbers in what the program and the reference are given
        assert cfg[key] == mc[key] == value, key
    assert set(mc) == set(PUBLISHED) | SCORER
    assert mc["first_expert"] == 0
    assert set(cfg["published"]) == set(CUT)
    assert cfg["published"]["num_hidden_layers"] == 88
    assert cfg["published"]["num_nextn_predict_layers"] == 1
    # every published width is kept
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel",
                "moe_latent_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "n_routed_experts", "routed_scaling_factor",
                "num_attention_heads", "num_key_value_heads", "head_dim"):
        assert mc[key] == PUBLISHED[key], key
    # the program's own defaults are the published config, whole
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.models.nemotron_h import NemotronHConfig

    whole = NemotronHConfig()
    for key, value in PUBLISHED.items():
        assert getattr(whole, key) == value, key
    assert "8 chips share each layer" in cfg["deployment"]
    assert "8 stages of 11 layers" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 8
    assert cfg["state_dtype"] == "float32"
    assert cfg["guarantees"] == load("benchmarks", "configs",
                                     "olmo-hybrid-7b-pp8.json")["guarantees"]
    assert set(cfg["limits"]) == {
        "score_gap_max", "score_gap_mean", "alert_mismatches", "lost_events",
        "duplicate_events", "reordered_events", "alerts_not_emitted",
        "alerts_not_stored", "failed_health", "compiles_in_window"}
    assert all(cfg["limits"][k] == 0 for k in cfg["limits"]
               if not k.startswith("score_gap"))
    assert set(cfg["limits_why"]) == {"score_gap_mean", "score_gap_max",
                                      "the eight counts"}
    # the program takes the file's `model_config` as it stands
    model = build_model(cfg["model"], **mc)
    assert model.layers == 11 and "".join(model.kinds) == "MEMEMEM*EME"
    assert sorted(model.windows) == ["k7", "v7"]
    # a run's contexts start past the window and never fill, and no
    # device's tick reaches 512 (PERF.md section 7, fault 3): no reseed
    traffic = load("benchmarks", "traffic", f"{CELL}.json")
    assert set(traffic) == set(load("benchmarks", "traffic",
                                    "olmo-hybrid-7b-pp8.steady.json"))
    slices = cfg["devices_per_tenant"] // cfg["frame_devices"]
    seconds = load("BENCHMARK.json")["run_seconds"]
    ticks = -(-(traffic["warm_beats"]
                + seconds * traffic["frames_per_s"]) // slices)
    assert slices == 3 and traffic["warm_beats"] == 3
    assert mc["window"] <= cfg["history_ticks"]
    assert mc["window"] + ticks < mc["context_positions"]
    assert cfg["history_ticks"] + ticks < 512


def test_the_cuts_bytes_as_reckoned():
    """The cut's arithmetic, from the program's own shapes: a Mamba-2
    layer 109.64M parameters, an expert layer 406.9M (352.3M of held
    experts, 5,505,024 an expert), the attention layer 35.7M, the
    embedding and the head over 16,384 rows 134.2M: 2,752M, 5.50 GB in
    bfloat16 (5.53 with the routers, the norms and a Mamba-2 layer's
    vectors in float32); a device's row 21.81 MB (five states of 4 MiB,
    five taps of 61,440 B, a context of 512 positions of 1 KiB), 8.40 GB
    over the 385 rows of a 384-device ring; 13.92 GB in all."""
    import jax

    from sitewhere_tpu.models import build_model

    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    model = build_model("nemotron-h-stream", **mc)

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert round(count(shapes["layer0"]) / 1e6, 2) == 109.64
    moe = shapes["layer1"]
    assert count(moe["experts"]["e0"]) == 5_505_024
    assert round(count(moe["experts"]) / 1e6, 1) == 352.3
    assert round(count(moe["shared"]) / 1e6, 1) == 44.0
    assert round(count([moe["latent_down"], moe["latent_up"]]) / 1e6, 1) \
        == 8.4
    assert round(count(moe["router"]) / 1e6, 1) == 2.1
    assert round(count(moe) / 1e6, 1) == 406.9
    assert round(count(shapes["layer7"]) / 1e6, 1) == 35.7
    assert round(count([shapes["embed"], shapes["head"]]) / 1e6, 1) == 134.2
    assert round(count(shapes) / 1e6) == 2752
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert round(2 * count(shapes) / 1e9, 2) == 5.50
    assert round(weights / 1e9, 2) == 5.53
    one = jax.eval_shape(lambda: model.init_state(1))
    row = {name: x.size * x.dtype.itemsize for name, x in one.items()}
    assert [row[f"s{l}"] for l in (0, 2, 4, 6, 9)] == [4_194_304] * 5
    assert one["s0"].shape == (1, 64, 128, 128)
    assert one["s0"].dtype == "float32"
    assert model.state_row_bytes == 4_194_304
    assert [row[f"c{l}"] for l in (0, 2, 4, 6, 9)] == [61_440] * 5
    assert one["c0"].dtype == "bfloat16"
    assert row["k7"] + row["v7"] == 524_288
    assert round(sum(row.values()) / 1e6, 2) == 21.81
    state = jax.eval_shape(lambda: model.init_state(385))
    table = sum(x.size * x.dtype.itemsize for x in state.values())
    assert round(table / 1e9, 2) == 8.40
    assert round((table + weights) / 1e9, 2) == 13.92
    # the reference's weights are laid out as the program's checkpoint,
    # and it counts the recurrent state that the program keeps
    counts = models.load("nemotron-h-stream")

    def dims(tree):
        return {k: dims(v) if isinstance(v, dict) else tuple(v[0])
                for k, v in tree.items()}

    assert dims(counts.param_shapes(mc)) == dims(model.param_shapes())
    assert counts.state_row_bytes(mc) == (5 * 4_194_304, 5 * 61_440)


def test_counts_from_the_equations():
    """A frame of 128 at 819 GB/s: every held weight (5.50 GB, the held
    experts 3.52 of it) once, the five states read and written (5.37
    GB), the context: about 13.3 ms least, bound by bytes."""
    counts = models.load("nemotron-h-stream")
    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    mamba = 4096 * 18560 + 4 * 10240 + 8192 * 4096
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256
    around = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    expert = 2 * 1024 * 2688
    head = 4096 * 16384
    resident, touched = counts._matrix_params(mc)
    assert resident == 5 * mamba + attention + 5 * (around + 64 * expert) \
        + 2 * head
    assert touched == 5 * mamba + attention \
        + 5 * (around + 22 * 64 / 512 * expert) + head
    assert counts._mean_positions(mc) == 304.5
    flops = counts.flops_per_event(mc)
    assert flops == 2 * touched + 5 * 5 * 8192 * 128 + 4 * 4096 * 304.5
    per_event = counts.bytes_per_event(mc, "float32")
    assert per_event == (2 * resident / 128 + 2 * 4096
                         + 2 * (5 * 4_194_304 + 5 * 61_440)
                         + 4 * 256 * 304.5 + 4 * 4096 + 8 + 4)
    peaks = load("benchmarks", "peaks.json")["TPU v5 lite"]
    least, bound = trace.least_seconds(128, flops, per_event, peaks)
    assert bound == "bytes" and 0.0128 < least < 0.0138
    assert 128 * flops / peaks["bf16_flops_per_s"] < least / 4


# the tiny size: hidden 128, 4 Mamba-2 heads of 64 in 2 groups of state
# 64 (two heads to a row of lanes), conv 4; 4 query heads on one of 128;
# 64 routed experts of which 8 are held, 6 a token, latent 128, experts
# of 128, a shared expert of 256; 64 of 512 bins
TINY = dict(hidden_size=128, mamba_num_heads=4, mamba_head_dim=64,
            ssm_state_size=64, n_groups=2, num_attention_heads=4,
            num_key_value_heads=1, n_routed_experts=64, num_experts_per_tok=6,
            n_routed_experts_held=8, moe_latent_size=128,
            moe_intermediate_size=128,
            moe_shared_expert_intermediate_size=256, vocab_size=512,
            vocab_held=64, window=16, context_positions=40)


def tiny_tree(dst):
    """The benchmark's data with this configuration cut to a size the
    CPU holds: 48 devices behind 3 gateways of 16 at `TINY`'s widths. The
    gap limits are a size's own: the file's are the chip's at the
    published widths, these are this size's."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    path = dst / "benchmarks" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["model_config"].update(TINY)
    cfg.update(devices_per_tenant=48, frame_devices=16, history_ticks=20,
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


def _cell_with(monkeypatch, change):
    """`_ssm_cell` with its arguments changed by `change(self, p, s, taps,
    xbc, dt, a)` on the way in."""
    from sitewhere_tpu.models.nemotron_h import NemotronHStreamModel

    real = NemotronHStreamModel._ssm_cell
    monkeypatch.setattr(
        NemotronHStreamModel, "_ssm_cell",
        lambda self, *args: real(self, *change(self, *args)))


def _the_decay_left_out(monkeypatch):
    """`a` 1: a state that never forgets."""
    import jax.numpy as jnp

    _cell_with(monkeypatch, lambda self, p, s, taps, xbc, dt, a: (
        p, s, taps, xbc, dt, jnp.ones_like(a)))
    return "score_gap_mean"


def _the_d_skip_left_out(monkeypatch):
    """`y = S C` without `D x`."""
    from sitewhere_tpu.models.nemotron_h import NemotronHStreamModel

    monkeypatch.setattr(NemotronHStreamModel, "_skip",
                        lambda self, p, y, x: y)
    return "score_gap_mean"


def _a_conv_that_forgets_its_oldest_tap(monkeypatch):
    """The oldest of the three stored inputs read as 0."""
    def change(self, p, s, taps, xbc, dt, a):
        oldest = self.cfg.conv_channels
        return p, s, taps.at[:, :oldest].set(0), xbc, dt, a

    _cell_with(monkeypatch, change)
    return "score_gap_mean"


def _relu_where_relu_squared(monkeypatch):
    """Every expert, the shared one too, `relu(x U) V`."""
    import jax

    from sitewhere_tpu.models.nemotron_h import NemotronHStreamModel

    monkeypatch.setattr(
        NemotronHStreamModel, "_mlp", lambda self, p, x: self._mm(
            jax.nn.relu(self._mm(x, p["up"])), p["down"]))
    return "score_gap_mean"


@pytest.mark.parametrize("fault", [
    _as_it_stands, _the_decay_left_out, _the_d_skip_left_out,
    _a_conv_that_forgets_its_oldest_tap, _relu_where_relu_squared])
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
                "state_absmax_p50", "expert_tokens_per_step",
                "expert_max_tokens_p50", "expert_one_tile_runs_per_step",
                "expert_weight_mb_per_step", "merge_fast_per_batch",
                "ring_ascending_per_dispatch"} <= set(got)
        assert "step_mfu" not in got and "state_rows_roofline" not in got
        assert "window_positions_p50" not in got
        assert 16 <= got["context_positions_p50"] <= 32
        assert 0 < got["state_absmax_p50"]
        # 8 held experts of 128 x 128 x 2, five layers: 1.31 MB a step
        assert got["expert_weight_mb_per_step"] == pytest.approx(
            5 * 8 * 2 * 128 * 128 * 2 / 1e6)
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
    mc.update(TINY, hidden_size=256, mamba_num_heads=8, vocab_held=512,
              window=24, context_positions=48)
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
