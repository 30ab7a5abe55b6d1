"""The DeepSeek-V3 configuration and its cell: the configuration's file
against the published config.json's numbers, the counts from the
equations, and the cell run from its files alone, tiny, on the CPU:
`correct` true as it stands, false with a fault planted under the timed
path (the held experts' part left out; a context that forgets its oldest
position).
"""

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import models, run
from benchmarks.readers import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "deepseek-v3-ep16", "deepseek-v3-ep16.steady"
SEED = 2 ** 31 + 29

# config.json of deepseek-ai/DeepSeek-V3, the numbers a width is made of
PUBLISHED = dict(
    hidden_size=7168, intermediate_size=18432, moe_intermediate_size=2048,
    num_attention_heads=128, num_key_value_heads=128, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_routed_experts=256, n_shared_experts=1,
    num_experts_per_tok=8, n_group=8, topk_group=4, routed_scaling_factor=2.5,
    vocab_size=129280, rope_theta=10000, rms_norm_eps=1e-06,
    max_position_embeddings=163840, num_nextn_predict_layers=1,
    moe_layer_freq=1, ep_size=1)
CUT = dict(num_hidden_layers=5, first_k_dense_replace=1,
           n_routed_experts_held=16, vocab_held=16160, mtp_modules=0)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_configuration_is_the_published_one_cut_as_it_says():
    cfg = load("benchmarks", "configs", f"{CONFIG}.json")
    entry = {c["name"]: c for c in load("BENCHMARK.json")["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json"
    mc = cfg["model_config"]
    for key, value in {**PUBLISHED, **CUT}.items():
        # the configuration as it is run, at the file's top level, and
        # the same numbers in what the program and the reference are given
        assert cfg[key] == mc[key] == value, key
    assert cfg["rope_scaling"] == mc["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert set(mc) - set(cfg) == {"window", "context_positions"}
    assert cfg["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280,
        "num_nextn_predict_layers": 1}
    assert cfg["guarantees"] == load("benchmarks", "configs",
                                     "stream-512k.json")["guarantees"]
    assert set(cfg["limits"]) == {
        "score_gap_max", "score_gap_mean", "alert_mismatches", "lost_events",
        "duplicate_events", "reordered_events", "alerts_not_emitted",
        "alerts_not_stored", "failed_health", "compiles_in_window"}
    # the program takes the file's `model_config` as it stands
    from sitewhere_tpu.models import build_model

    model = build_model(cfg["model"], **mc)
    assert model.layers == 5 and model.cfg.experts_held == 16
    # a run's contexts start at the window and never fill: one position in
    # the warm-up beats, then a device's share of the window's frames
    traffic = load("benchmarks", "traffic", f"{CELL}.json")
    slices = cfg["devices_per_tenant"] // cfg["frame_devices"]
    seconds = load("BENCHMARK.json")["run_seconds"]
    ticks = -(-(traffic["warm_beats"]
                + seconds * traffic["frames_per_s"]) // slices)
    assert mc["window"] + ticks <= mc["context_positions"]
    assert traffic["frames_per_s"] <= 25


def test_counts_from_the_equations():
    counts = models.load("dsv3-stream")
    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    resident, touched = counts._matrix_params(mc)
    # ISSUE 28's table: 187.1M of MLA, 396.4M of dense MLP, 44.04M an
    # expert, 1.8M of router, 115.8M of head over an eighth of the rows
    mla = 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168
    expert, dense, head = 3 * 7168 * 2048, 3 * 7168 * 18432, 7168 * 16160
    assert round(mla / 1e6, 1) == 187.1 and round(expert / 1e6, 2) == 44.04
    assert resident == (mla + dense) + 4 * (mla + 7168 * 256
                                            + 17 * expert) + 2 * head
    assert touched == (mla + dense) + 4 * (mla + 7168 * 256
                                           + 1.5 * expert) + head
    assert round(2 * resident / 1e9, 1) == 9.1           # GB at 2 B
    flops = counts.flops_per_event(mc)
    assert flops == 2 * touched + 5 * 278528 * 128
    assert 3.4e9 < 2 * touched < 3.5e9
    per_event = counts.bytes_per_event(mc, "float32")
    assert per_event == (2 * resident / 1024 + 5 * 1152 * 129
                         + 4 * 7168 + 8 + 4)
    peaks = load("benchmarks", "peaks.json")["TPU v5 lite"]
    least, bound = trace.least_seconds(1024, flops, per_event, peaks)
    assert bound == "flops" and 0.018 < least < 0.020     # ISSUE: 18 ms
    # the weights are laid out as the program's checkpoint
    from sitewhere_tpu.models import build_model

    def shapes(tree):
        return {k: shapes(v) if isinstance(v, dict) else tuple(v[0])
                for k, v in tree.items()}

    small = {**mc, "num_hidden_layers": 2, "mtp_modules": 1}
    assert shapes(counts.param_shapes(small)) == shapes(
        build_model("dsv3-stream", **small).param_shapes())


def tiny_tree(dst):
    """The benchmark's data with this configuration cut to a size the
    CPU holds: 64 devices behind 4 gateways of 16, hidden 64, 4 heads, 16
    experts of width 256 of which 8 are held, vocabulary 64, 1 dense + 2
    expert layers. The gap limits are a size's own: the file's are the
    chip's at the published widths, these are this size's (as it stands
    it reads 0.0033 and 0.00008; with the experts' part left out 0.024
    and 0.0063, with a context's oldest position gone 0.016 and 0.0018)."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    path = dst / "benchmarks" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["model_config"].update(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=256,
        num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=16, n_group=2, topk_group=1,
        num_experts_per_tok=4, vocab_size=64, vocab_held=64,
        n_routed_experts_held=8, first_expert=4, window=16,
        context_positions=24)
    cfg.update(devices_per_tenant=64, frame_devices=16, history_ticks=20,
               anomaly_rate=0.02, threshold=4.5)
    cfg["limits"].update(score_gap_max=0.01, score_gap_mean=0.0005)
    path.write_text(json.dumps(cfg))
    return str(dst)


@pytest.fixture(autouse=True)
def as_the_other_rehearsals(monkeypatch, tmp_path):
    """The compile cache placed from outside and one settle thread, as
    tests/benchmarks/test_bench_run.py's runs have them, and why."""
    import jax
    from concurrent.futures import ThreadPoolExecutor

    from sitewhere_tpu.scoring import server

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    one = ThreadPoolExecutor(max_workers=1, thread_name_prefix="settle-1")
    monkeypatch.setattr(server, "SETTLE_POOL", one)
    yield
    one.shutdown(wait=False)
    for k, v in before.items():
        jax.config.update(k, v)


def _as_it_stands(monkeypatch):
    return None


def _held_experts_left_out(monkeypatch):
    """The routed experts' part of every expert layer left out."""
    import jax.numpy as jnp

    from sitewhere_tpu.models.dsv3 import Dsv3StreamModel

    real = Dsv3StreamModel.routed

    def broken(self, p, x, idx, w, live):
        out, counts = real(self, p, x, idx, w, live)
        return jnp.zeros_like(out), counts

    monkeypatch.setattr(Dsv3StreamModel, "routed", broken)
    return "score_gap_mean"


def _context_forgets_its_oldest(monkeypatch):
    """A step that attends to a context whose oldest position is gone."""
    from sitewhere_tpu.models.dsv3 import Dsv3StreamModel

    real = Dsv3StreamModel._attend_decode

    def broken(self, p, q_nope, q_rope, entry, ctx, pos):
        return real(self, p, q_nope, q_rope, entry, ctx.at[:, 0].set(0), pos)

    monkeypatch.setattr(Dsv3StreamModel, "_attend_decode", broken)
    return "score_gap_mean"


@pytest.mark.parametrize("fault", [_as_it_stands, _held_experts_left_out,
                                   _context_forgets_its_oldest])
def test_cell_tiny_on_cpu_from_its_files_alone(tmp_path, monkeypatch, fault):
    failing = fault(monkeypatch)
    result, info = run.run_cell(CELL, SEED, 1.0, True, "cpu",
                                root=tiny_tree(tmp_path))
    frames = load("benchmarks", "traffic", f"{CELL}.json")["frames_per_s"]
    assert info["frames"] == frames and info["rejected_events"] == 0
    assert result["attempted"] == frames * 16 and result["failed"] == 0
    # every served score is compared, the warm-up beats' too
    assert info["compared_events"] == (4 + frames) * 16
    if failing is None:
        assert result["correct"], result["checks"]
        # what the counters give is in a traced run's line; a CPU trace
        # has no device plane, so the trace's readers leave theirs out
        got = {k: v["value"] for k, v in result["metrics"].items()}
        assert {"expert_tokens_per_step", "expert_max_tokens_p50",
                "context_positions_p50", "events_per_dispatch"} <= set(got)
        assert "step_mfu" not in got
        # 16 tokens x 4 experts x 2 expert layers, half of them held
        assert 0.5 * 64 < got["expert_tokens_per_step"] < 1.5 * 64
        assert 16 <= got["context_positions_p50"] <= 23
    else:
        assert result["correct"] is False
        check = result["checks"][failing]
        assert check["value"] > check["limit"], result["checks"]


def test_control_stands_clear_of_the_configurations_precision():
    """The reference one precision down against the reference in the
    configuration's: at a small size its mean gap is several times what
    bfloat16 itself stands from float32, which is the room a limit needs
    between the program and the control. (The limits in the file are the
    chip's, at the published widths, where every gap is larger: the
    control's readings there are in PERF.md, section 2.)"""
    from benchmarks import compare, gen

    cfg = load("benchmarks", "configs", f"{CONFIG}.json")
    reference = models.load(cfg["model"])
    mc = json.loads(json.dumps(cfg["model_config"]))
    mc.update(hidden_size=256, intermediate_size=512,
              moe_intermediate_size=128, num_hidden_layers=3,
              num_attention_heads=8, q_lora_rank=96, kv_lora_rank=64,
              qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
              n_routed_experts=32, n_routed_experts_held=8, vocab_size=512,
              vocab_held=512, window=16, context_positions=32)
    fleet = gen.Fleet(5, 0, 24, 0.02, 12.0)
    hist = np.stack([fleet.values(k, spikes=False) for k in range(20)], 1)
    frames = np.stack([fleet.values(20 + k) for k in range(12)])
    fed = np.ones(frames.shape, bool)
    params = reference.tenant_params(5, 0, mc)

    def scores(dtype):
        return reference.run(params, hist, frames, fed, mc, dtype)

    stated = scores(cfg["compute_dtype"])
    _, own = compare.score_gaps(stated, scores("float32"))
    _, control = compare.score_gaps(
        scores(compare.LOWER[cfg["compute_dtype"]]), stated)
    assert control > 5 * own > 0
