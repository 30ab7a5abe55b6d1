"""chip_smoke.py's body, tiny, on the conftest's 8 virtual CPU devices —
the control flow the chip check runs at full size — plus the bring-up
contracts that have no chip in them: the compile-cache helper,
chip_smoke.py's refusals of a platform and of a chip without a peak.

The other two no-fallback contracts are pinned where their subjects
already had tests: `mesh_from_spec` raising on a spec that does not fit
(tests/test_mesh_serving.py) and a selected fused step whose compile
fails raising out of the ring (tests/test_pallas.py).
"""

import os

import jax
import pytest

import chip_smoke
from sitewhere_tpu.utils.backend import use_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a tick every 0.25 s: at 64 devices a tenant's overload bar is two
# ticks of backlog, so a shorter period turns any hiccup of a loaded
# test machine into a frame shed at ingress (seen at 0.05 s)
TINY = chip_smoke.Sizes(
    devices=64, ticks=6, pool_tenants=2, pool_devices=64, kernel_bucket=256,
    anomaly_rate=0.05, interval_s=0.25, dsv3_devices=32, dsv3_config=dict(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=2, first_k_dense_replace=1, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, n_group=2,
        topk_group=1, num_experts_per_tok=4, vocab_size=64,
        n_routed_experts_held=4, mtp_modules=0, window=64,
        context_positions=96),
    laguna_devices=32, laguna_config=dict(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_hidden_layers=3,
        num_key_value_heads=2, head_dim=64,
        num_attention_heads_per_layer=[4, 6, 4], num_experts=16,
        num_experts_per_tok=4, vocab_size=64, num_experts_held=4,
        sliding_window=32, window=64, context_positions=96,
        layer_types=["full_attention", "sliding_attention",
                     "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"],
        gating_types=["per_head"] * 3))


def test_smoke_body_tiny_on_cpu(monkeypatch, tmp_path):
    # cache placed from outside: the helper must then set nothing, so
    # the rest of the test session keeps JAX's config as it found it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    summary = chip_smoke.run_smoke("cpu", TINY)
    assert jax.config.jax_compilation_cache_dir == before
    assert summary["ok"], summary
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 8}
    assert summary["compile_cache"] == str(tmp_path)
    a, b, c, d, e = (summary["phases"][k] for k in "ABCDE")
    want = TINY.devices * TINY.ticks
    assert a["sent"] == a["scored"] == a["published"] == want
    assert a["alerts"] >= 1 and a["compiles_after_warmup"] == 0
    assert a["feeder_exit"] == 0
    assert a["table_moves"] == []
    assert sorted(a["state_layouts"]) == ["row"]
    assert b["ok"] and b["skipped"] == "pallas_ok false on cpu"
    assert c["mesh"] == {"data": 2, "model": 2}
    assert c["scored_per_tenant"] == [want]
    assert c["megabatch_dispatches"] > 0 and c["swap_version"] == 1
    # the CPU reports no memory, so its session builds weights of its own;
    # the first swap still seeds again, and the step moves no context
    assert d["sent"] == d["scored"] == d["published"] == 32 * TINY.ticks
    assert isinstance(d["table_moves"], list) and d["assignments_held"] > 0
    assert d["compiles_after_warmup"] == 0 and d["device_bytes"] is None
    assert {"ctx0", "ctx1", "hn", "pos"} <= set(d["state_layouts"])
    # `laguna-stream` the same way: a windowed and a full context a layer
    assert e["sent"] == e["scored"] == e["published"] == 32 * TINY.ticks
    assert isinstance(e["table_moves"], list) and e["assignments_held"] > 0
    assert e["compiles_after_warmup"] == 0
    assert {"k0", "v0", "k1", "v1", "k2", "v2", "hn", "pos"} \
        <= set(e["state_layouts"])
    assert e["state_layouts"]["k1"].startswith("[1025, 32, 128]")
    assert e["state_layouts"]["k2"].startswith("[1025, 96, 128]")


@pytest.mark.parametrize("line,found", [
    # the parent's step at the cell's size (PERF.md, PR 27): a leaf that
    # rests column-major is copied to row-major and back
    ("  %copy.6 = f32[524289,64]{1,0:T(8,128)} copy(%state__c0__.1), "
     "sharding={replicated}", ["%copy.6 copy[524289,64]"]),
    ("  ROOT %transpose.1 = f32[64,524289]{1,0} transpose(%x), "
     "dimensions={1,0}", ["%transpose.1 transpose[64,524289]"]),
    # not a move of a table: the scatter itself, a bucket's rows, a
    # one-dimensional leaf staged into faster memory
    ("  %fusion.6 = f32[524289,128]{1,0:T(8,128)} fusion(%state__hc__.1, "
     "%gte.10, %copy.2), kind=kCustom", []),
    ("  %copy.2 = f32[16384,128]{1,0:T(8,128)} copy(%fusion.3)", []),
    ("  %copy-done.2 = f32[524289]{0:T(1024)S(1)} copy-done(%copy-start.2)",
     []),
    ("  %copy.8 = f32[524289]{0} copy(%state__pred__.1)", []),
])
def test_table_moves_reads_whole_table_copies_off_the_compiled_text(
        line, found):
    assert chip_smoke._table_moves(line + "\n", 524289) == found


def test_smoke_refuses_the_wrong_platform():
    """`python chip_smoke.py` hard-codes "tpu": on a CPU it must stop at
    the platform check, before any phase, with a non-zero exit."""
    with pytest.raises(SystemExit) as exc:
        chip_smoke.run_smoke("tpu", TINY)
    assert exc.value.code not in (0, None)


def test_smoke_reads_the_peak_from_the_benchmarks_table():
    # the v5e as JAX names it, from benchmarks/peaks.json
    assert chip_smoke.peak_bf16_flops("TPU v5 lite") == 197e12
    chip_smoke.require_peak("TPU v5 lite")
    # a device the table does not know stops the smoke before any phase
    assert chip_smoke.peak_bf16_flops("cpu") is None
    with pytest.raises(SystemExit) as exc:
        chip_smoke.require_peak("cpu")
    assert exc.value.code == 2


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path      # fixed: same every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
