"""The ring step of `dsv3-stream` compiled for a TPU v5e that is described
and not attached (the TPU's compiler is installed here), at the published
widths with one leading and one expert layer and a small fleet: the
compiled step copies and transposes no context leaf, which rests
row-major in whole lane tiles. Nothing runs, so nothing here is a time.

The topology is described inside a fixture, never at import, and every
test that needs it is in this one file (one process loads the TPU's
library and keeps it).
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

ROWS, BUCKET = 1025, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_compiled_step_moves_no_context_leaf(one_chip):
    from chip_smoke import _table_moves
    from sitewhere_tpu.models import build_model
    from sitewhere_tpu.scoring.stream import streaming_step

    model = build_model("dsv3-stream", num_hidden_layers=2,
                        first_k_dense_replace=1, n_routed_experts_held=16,
                        vocab_held=16160, mtp_modules=0)

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = described(jax.eval_shape(lambda: model.init_state(ROWS)))
    dev = jax.ShapeDtypeStruct((BUCKET,), jnp.int32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((BUCKET,), jnp.float32, sharding=one_chip)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(streaming_step(model, jnp.float32),
                           donate_argnums=(1,)).lower(
            params, state, dev, v).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    hlo = compiled.as_text()
    assert _table_moves(hlo, ROWS) == []
    width = model.cfg.entry_width
    assert width == 640
    layouts = set(re.findall(
        rf"bf16\[{ROWS},192,{width}\]\{{([\d,]+)", hlo))
    assert layouts == {"2,1,0"}
    mem = compiled.memory_analysis()
    # the donated state comes back in its own buffers
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes
