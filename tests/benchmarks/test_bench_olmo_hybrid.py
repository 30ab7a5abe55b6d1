"""The Olmo-Hybrid-7B configuration and its cell: the configuration's
file against the published config.json's numbers, the cut's bytes and
the counts from the equations, and the cell run from its files alone,
tiny, on the CPU: `correct` true as it stands, false with a fault
planted under the timed path (the decay left out, `beta` without its
factor 2, a conv that forgets its oldest tap, a step that returns the
matrix states unchanged).
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
CONFIG, CELL = "olmo-hybrid-7b-pp8", "olmo-hybrid-7b-pp8.steady"
SEED = 2 ** 31 + 41
TINY_FRAMES = 10          # frames a second of the tiny cell on the CPU

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# config.json of allenai/Olmo-Hybrid-7B (the catalog's row), whole
PUBLISHED = dict(
    model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
    intermediate_size=11008, num_hidden_layers=32, num_attention_heads=30,
    num_key_value_heads=30, hidden_act="silu", max_position_embeddings=65536,
    attention_bias=False, rms_norm_eps=1e-06, tie_word_embeddings=False,
    layer_types=PERIOD * 8, linear_num_key_heads=30,
    linear_num_value_heads=30, linear_key_head_dim=96,
    linear_value_head_dim=192, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
CUT = dict(num_hidden_layers=4, layer_types=PERIOD)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_configuration_is_the_published_one_cut_as_it_says():
    cfg = load("benchmarks", "configs", f"{CONFIG}.json")
    entry = {c["name"]: c for c in load("BENCHMARK.json")["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    mc = cfg["model_config"]
    for key, value in {**PUBLISHED, **CUT}.items():
        # the configuration as it is run, at the file's top level, and
        # the same numbers in what the program and the reference are given
        assert cfg[key] == mc[key] == value, key
    assert set(mc) - set(cfg) == {"window", "context_positions"}
    assert set(mc) - {"window", "context_positions"} == set(PUBLISHED)
    assert set(cfg["published"]) == set(CUT)
    assert cfg["published"]["num_hidden_layers"] == 32
    # the program's own defaults are the published config, whole: four
    # layers of it are the file's four
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.models.olmo_hybrid import OlmoHybridConfig

    whole = OlmoHybridConfig()
    for key, value in PUBLISHED.items():
        assert getattr(whole, key) == value, key
    assert "8 stages of 4 layers" in cfg["deployment"]
    assert cfg["state_dtype"] == "float32"
    assert cfg["guarantees"] == load("benchmarks", "configs",
                                     "laguna-s-2.1-ep8.json")["guarantees"] \
        == load("benchmarks", "configs", "deepseek-v3-ep16.json")["guarantees"]
    assert set(cfg["limits"]) == {
        "score_gap_max", "score_gap_mean", "alert_mismatches", "lost_events",
        "duplicate_events", "reordered_events", "alerts_not_emitted",
        "alerts_not_stored", "failed_health", "compiles_in_window"}
    assert all(cfg["limits"][k] == 0 for k in cfg["limits"]
               if not k.startswith("score_gap"))
    # the program takes the file's `model_config` as it stands
    model = build_model(cfg["model"], **mc)
    assert model.layers == 4 and model.kinds == PERIOD
    assert sorted(model.windows) == ["k3", "v3"]
    # a run's contexts start past the window and never fill: no reseed
    traffic = load("benchmarks", "traffic", f"{CELL}.json")
    assert set(traffic) == set(load("benchmarks", "traffic",
                                    "laguna-s-2.1-ep8.steady.json"))
    slices = cfg["devices_per_tenant"] // cfg["frame_devices"]
    seconds = load("BENCHMARK.json")["run_seconds"]
    ticks = -(-(traffic["warm_beats"]
                + seconds * traffic["frames_per_s"]) // slices)
    assert slices == 3 and traffic["warm_beats"] == 3
    assert mc["window"] <= cfg["history_ticks"]
    assert mc["window"] + ticks <= mc["context_positions"]
    assert traffic["frames_per_s"] <= 42


def test_the_cuts_bytes_as_reckoned():
    """ISSUE 35's arithmetic, from the program's own shapes: a linear
    layer 88.75M parameters and its MLP 126.81M, a period 832.5M, with
    the whole vocabulary 3.21 GB; a device 12.75 MB, of which the three
    matrix states are 6.64 and the context 5.90; 9.80 GB over the 769
    rows of a 768-device ring."""
    import jax

    from sitewhere_tpu.models import build_model

    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    model = build_model("olmo-hybrid-stream", **mc)

    def millions(tree):
        return sum(x.size for x in jax.tree.leaves(tree)) / 1e6

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mixer = {k: v for k, v in shapes["layer0"].items()
             if k in ("q", "k", "v", "g", "o", "a", "b", "conv")}
    assert round(millions(mixer), 2) == 88.75
    assert round(millions(shapes["layer0"]["mlp"]), 2) == 126.81
    assert [round(millions(shapes[f"layer{l}"]), 1)
            for l in range(4)] == [215.6, 215.6, 215.6, 185.8]
    assert round(millions([shapes[f"layer{l}"] for l in range(4)]), 1) == 832.5
    assert round(millions([shapes["embed"], shapes["head"]]), 1) == 770.7
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert round(weights / 1e9, 2) == 3.21
    one = jax.eval_shape(lambda: model.init_state(1))
    row = {name: x.size * x.dtype.itemsize for name, x in one.items()}
    assert row["s0"] == row["s1"] == row["s2"] == 2_211_840
    assert one["s0"].shape == (1, 15, 96, 384) and one["s0"].dtype == "float32"
    assert row["c0"] == 69_120 and one["c0"].dtype == "bfloat16"
    assert row["k3"] == row["v3"] == 384 * 3840 * 2
    assert round(sum(row.values()) / 1e6, 2) == 12.75
    state = jax.eval_shape(lambda: model.init_state(769))
    table = sum(x.size * x.dtype.itemsize for x in state.values())
    assert round(table / 1e9, 2) == 9.80
    # the reference's weights are laid out as the program's checkpoint,
    # and it counts the recurrent state that the program keeps
    counts = models.load("olmo-hybrid-stream")

    def dims(tree):
        return {k: dims(v) if isinstance(v, dict) else tuple(v[0])
                for k, v in tree.items()}

    assert dims(counts.param_shapes(mc)) == dims(model.param_shapes())
    assert counts.state_row_bytes(mc) == (3 * 2_211_840, 3 * 69_120)


def test_counts_from_the_equations():
    counts = models.load("olmo-hybrid-stream")
    mc = load("benchmarks", "configs", f"{CONFIG}.json")["model_config"]
    linear = (3840 * 11520 + 2 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520)
    mlp, head = 3 * 3840 * 11008, 3840 * 100352
    assert counts._matrix_params(mc) == 3 * (linear + mlp) \
        + 4 * 3840 * 3840 + mlp + head
    # a full layer attends to 97..384 positions over a run from a seeded
    # window to a full context
    assert counts._mean_positions(mc) == 240.5
    flops = counts.flops_per_event(mc)
    assert flops == 2 * counts._matrix_params(mc) + 3 * 7 * 552960 \
        + 4 * 3840 * 240.5
    assert 2.4e9 < flops < 2.5e9                  # ISSUE: about 2.4 GFLOP
    per_event = counts.bytes_per_event(mc, "float32")
    # the state read once and written once, whatever the program does
    assert per_event == (2 * counts._matrix_params(mc) / 256 + 2 * 3840
                         + 2 * (3 * 2_211_840 + 3 * 69_120)
                         + 4 * 3840 * 240.5 + 4 * 3840 + 8 + 4)
    assert 26.5e6 < per_event < 27.5e6            # ISSUE: about 27 MB
    peaks = load("benchmarks", "peaks.json")["TPU v5 lite"]
    least, bound = trace.least_seconds(256, flops, per_event, peaks)
    assert bound == "bytes" and 0.008 < least < 0.009   # ISSUE: 8 to 9 ms
    assert 256 * flops / peaks["bf16_flops_per_s"] < least / 2


def tiny_tree(dst):
    """The benchmark's data with this configuration cut to a size the
    CPU holds: 48 devices behind 3 gateways of 16, hidden 128, 2 heads of
    64 in the full layer, 2 matrix states of 32 x 64 a linear layer (one
    row of 128 lanes), MLP 256, vocabulary 64. The gap limits are a
    size's own: the file's are the chip's at the published widths, these
    are this size's (as it stands it reads 0.00037 and 0.000011: the
    program's products are the reference's, operand for operand; the
    four faults read 0.012 to 0.025 and 0.00098 to 0.0044)."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst / "BENCHMARK.json")
    path = dst / "benchmarks" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["model_config"].update(
        hidden_size=128, intermediate_size=256, num_attention_heads=2,
        num_key_value_heads=2, vocab_size=64, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=32,
        linear_value_head_dim=64, window=16, context_positions=40)
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
    """`_gdn_cell` with its arguments changed by `change(self, p, s, taps,
    z, alpha, beta)` on the way in."""
    from sitewhere_tpu.models.olmo_hybrid import OlmoHybridStreamModel

    real = OlmoHybridStreamModel._gdn_cell
    monkeypatch.setattr(
        OlmoHybridStreamModel, "_gdn_cell",
        lambda self, *args: real(self, *change(self, *args)))


def _the_decay_left_out(monkeypatch):
    """`alpha` 1: a state that never forgets."""
    import jax.numpy as jnp

    _cell_with(monkeypatch, lambda self, p, s, taps, z, alpha, beta: (
        p, s, taps, z, jnp.ones_like(alpha), beta))
    return "score_gap_mean"


def _beta_without_its_factor_two(monkeypatch):
    """`beta` in (0, 1) where `linear_allow_neg_eigval` says (0, 2)."""
    _cell_with(monkeypatch, lambda self, p, s, taps, z, alpha, beta: (
        p, s, taps, z, alpha, beta / 2))
    return "score_gap_mean"


def _a_conv_that_forgets_its_oldest_tap(monkeypatch):
    """The oldest of the three stored inputs read as 0."""
    def change(self, p, s, taps, z, alpha, beta):
        oldest = self.cfg.conv_channels
        return p, s, taps.at[:, :oldest].set(0), z, alpha, beta

    _cell_with(monkeypatch, change)
    return "score_gap_mean"


def _a_step_that_returns_the_states_unchanged(monkeypatch):
    """The ring step writes back the matrix states it read (seeding, a
    scan of the same cell with no ring in it, still moves them)."""
    from sitewhere_tpu.scoring import stream

    real = stream.RowsInTurn.write

    def write(self, rows, then):
        if rows.ndim == 4:
            rows = stream._rows(self.table, self._dev)
        return real(self, rows, then)

    monkeypatch.setattr(stream.RowsInTurn, "write", write)
    return "score_gap_mean"


@pytest.mark.parametrize("fault", [
    _as_it_stands, _the_decay_left_out, _beta_without_its_factor_two,
    _a_conv_that_forgets_its_oldest_tap,
    _a_step_that_returns_the_states_unchanged])
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
                "state_rewritten_mb_per_step", "state_absmax_p50",
                "merge_fast_per_batch", "ring_ascending_per_dispatch"} \
            <= set(got)
        assert "step_mfu" not in got and "expert_tokens_per_step" not in got
        assert "window_positions_p50" not in got
        assert 16 <= got["context_positions_p50"] <= 32
        # no ratio of two of a one-second window's counts is held to a
        # number here (PERF.md section 7 on test_bench_dsv3.py): the
        # counter's arithmetic is tests/test_streaming.py's
        assert got["state_rewritten_mb_per_step"] > 0
        assert 0 < got["state_absmax_p50"] < 1
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
    mc.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
              num_key_value_heads=4, vocab_size=512, linear_num_key_heads=4,
              linear_num_value_heads=4, linear_key_head_dim=32,
              linear_value_head_dim=64, window=24, context_positions=48)
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
