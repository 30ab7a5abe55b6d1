"""The ring step of `dsv3-stream` compiled for a TPU v5e that is described
and not attached (the TPU's compiler is installed here), at the published
widths with one leading and one expert layer and a small fleet: the
compiled step copies and transposes no context leaf, which rests
row-major in whole lane tiles, and reads it where it rests (one
`context_rows` call a layer over the table as both keys and values
behind the append of the position's own entries, no gathered rows); the
ring step of `laguna-stream` at its
benchmark configuration's own size (five layers, 769 rows of 12 MiB),
held to the same and to the chip's memory; the ring step of
`olmo-hybrid-stream` at its configuration's own size (four layers, 769
rows of 12.75 MB, three matrix states among them), held to the same
(both read their contexts where they rest: one `context_rows` kernel a
layer, no gathered rows); the ring step of `lfm2-stream` at its
configuration's own size (eight layers, 2,561 rows, six expert layers of
64 held experts: 1,152 expert leaves, none copied; its contexts read
where they rest, key-value heads of 64 two to a lane tile); and the ring
step of `lstm-stream` at `stream-512k`'s own size, which moves rows of
ONE table. In the three steps with held experts each
`expert_tiles` kernel's Mosaic module is read back: its step picks the
next weight block in a tree of branches, not one a held expert. Nothing
runs, so nothing here is a time.

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
FLEET_ROWS, FRAME = 524289, 16384       # `stream-512k`: the table, a frame


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_step(model, rows, bucket, one_chip, out_dtype):
    """The ring step of `model` over a table of `rows` rows and a bucket
    of `bucket`, compiled for the described chip: (state shapes, the
    compiled step)."""
    from sitewhere_tpu.scoring.stream import streaming_step

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = described(jax.eval_shape(lambda: model.init_state(rows)))
    dev = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((bucket,), jnp.float32, sharding=one_chip)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(streaming_step(model, out_dtype),
                           donate_argnums=(1,)).lower(
            params, state, dev, v).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return state, compiled


@pytest.fixture(scope="module")
def step(one_chip):
    """(model, state shapes, the ring step compiled for the described
    chip): compiled once for every test of this file."""
    from sitewhere_tpu.models import build_model

    model = build_model("dsv3-stream", num_hidden_layers=2,
                        first_k_dense_replace=1, n_routed_experts_held=16,
                        vocab_held=16160, mtp_modules=0)
    state, compiled = _compile_step(model, ROWS, BUCKET, one_chip,
                                    jnp.float32)
    return model, state, compiled


def test_compiled_step_moves_no_context_leaf(step):
    """The latent context (`bf16[1025, 192, 640]` a layer: 576 values in
    640 lanes) is neither copied nor transposed, and is not gathered at
    all: each layer's attention is ONE `context_rows` call that takes the
    layer's table, as keys and as values, behind the append of the
    position's own entries (ops/context_kernel.py's one-table form), and
    hands back the weighted latents `[256, 128, 512]` in bfloat16; a
    table is a parameter or the append's in-place scatter, nothing of a
    frame's gathered rows `[256, 192, 640]` exists, and the donated state
    comes back in its own buffers."""
    from chip_smoke import _table_moves
    from sitewhere_tpu.ops import context_kernel

    model, state, compiled = step
    hlo = compiled.as_text()
    lines = hlo.splitlines()
    assert _table_moves(hlo, ROWS) == []
    width = model.cfg.entry_width
    assert width == 640
    table = f"bf16[{ROWS},192,{width}]"
    assert context_kernel.fits_latent((ROWS, 192, width), jnp.bfloat16, 128,
                                      512)
    layouts = set(re.findall(re.escape(table) + r"\{([\d,]+)", hlo))
    assert layouts == {"2,1,0"}
    calls = [line for line in lines if "tpu_custom_call" in line
             and "context_rows" in line]
    assert len(calls) == model.layers == 2
    # (the table is handed over once for each row of a grid step, the
    # same buffer each time)
    assert all(line.count(table) == context_kernel.LATENT_ROWS and re.search(
        rf"= bf16\[{BUCKET},128,512\]\S* custom-call\(", line)
        and "output_to_operand_aliasing" not in line
        and "mla_attend" in line for line in calls), calls
    # what makes a value of a table's or a frame's rows' shape: the
    # parameter, the append (a scatter, fused in place) and nothing else
    made = {m for line in lines for m in re.findall(
        r"= bf16\[\d+,192,640\]\S* ([\w-]+)\(", line)}
    assert made == {"parameter", "scatter", "fusion"}, made
    assert all("ctx_append" in line for line in lines if re.search(
        r"= bf16\[\d+,192,640\]\S* (?:fusion|scatter)\(", line))
    assert not re.search(rf"\[{BUCKET},192,640\]", hlo)
    assert not [line for line in lines if " gather(" in line
                and table in line]
    mem = compiled.memory_analysis()
    # the donated state comes back in its own buffers
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes
    # the scratch was the gathered contexts': `temp_size_in_bytes` read
    # 122,687,488 while each layer's rows were gathered, and reads
    # 51,384,832 here
    assert mem.temp_size_in_bytes < 0.07e9


def _computations(hlo: str) -> tuple[dict, str]:
    """name -> lines of each computation of an HLO module, and the
    entry's name."""
    comps, entry, name = {}, None, None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif name is not None:
            comps[name].append(line)
    return comps, entry


_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=(%[\w.\-]+)|branch_computations=\{([^}]*)\}")


def _inside_whiles(comps: dict) -> set:
    """Computations that run inside some `while`: its body and
    condition, and whatever those call."""
    called = {name: {c for one, many in _CALLS.findall(" ".join(lines))
                     for c in re.findall(r"%[\w.\-]+", one + " " + many)}
              for name, lines in comps.items()}
    inside, todo = set(), [
        c for lines in comps.values() for line in lines
        if " while(" in line
        for c in re.findall(r"(?:body|condition)=(%[\w.\-]+)", line)]
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo.extend(called.get(c, ()))
    return inside


def _expert_leaves_read_once(hlo: str, held: int, layers: list,
                             hidden: int, inter: int,
                             names: tuple = ("gate", "up", "down")) -> None:
    """The held experts as one grouped pass a layer: each of an expert
    layer's `len(names) * held` leaves goes to a kernel (or a product) of the
    entry computation, outside any `while`; the `while`s that remain are
    the overflow's, ONE a layer (further passes of the same kind, tile
    `n` of every held expert at once), under `moe_experts`, and only they
    read a leaf a second time; no leaf is copied or sliced."""
    comps, entry = _computations(hlo)
    inside = _inside_whiles(comps)
    assert entry not in inside
    body = comps[entry]
    # the entry's lines by the names they hold, whole (1,152 leaves at 64
    # held in six layers: one pass over the text, not one a leaf)
    named = {}
    for line in body:
        if " parameter(" not in line:
            for name in set(re.findall(r"%([\w.]+)", line)):
                named.setdefault(name, []).append(line)
    for layer in layers:
        leaves = re.findall(rf"(params__layer{layer}____experts____e\d+____"
                            rf"(?:{'|'.join(names)})__[.\d]*): bf16", hlo)
        assert len(set(leaves)) == len(names) * held
        for leaf in set(leaves):
            uses = named.get(leaf, [])
            # the kernel (or a product) takes the leaf as it rests, or the
            # compiler fetches it ahead into fast memory, whole, for the
            # kernel and the overflow's loop after it; every other use
            # hands it to that loop
            reads = [line for line in uses
                     if "tpu_custom_call" in line or " convolution(" in line
                     or " dot(" in line or "kind=kOutput" in line]
            ahead = [line for line in uses
                     if " slice-start(" in line or " copy-start(" in line]
            assert len(reads) == 1 or (not reads and ahead), (leaf, uses)
            assert all("moe_experts" in line for line in reads)
            assert all(" conditional(" in line or " while(" in line
                       or " tuple(" in line for line in uses
                       if line not in reads and line not in ahead), (leaf,
                                                                     uses)
    # (a step's other kernels, `context_rows` and `state_rows`, are
    # attention's and a recurrence's)
    kernels = [line for line in body if "tpu_custom_call" in line
               and "context_rows" not in line and "state_rows" not in line]
    assert len(kernels) == len(layers)
    assert all("moe_experts" in line for line in kernels)
    whiles = [line for lines in comps.values() for line in lines
              if " while(" in line]
    assert len(whiles) == len(layers)
    assert all("moe_experts" in line for line in whiles)
    moved = [line for line in hlo.splitlines() if re.search(
        rf"= bf16\[(?:{hidden},{inter}|{inter},{hidden})\]\S* "
        r"(?:copy|slice|dynamic-slice)\(", line)]
    assert moved == []


def _mosaic_modules(calls: list) -> list:
    """The Mosaic module of each distinct kernel among the HLO lines
    `calls`, parsed back from its custom call's serialized body."""
    import base64
    import json

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir
    from jaxlib.mlir.passmanager import PassManager

    ctx = mlir.JaxIrContext()
    ctx.append_dialect_registry(mlir.upstream_dialects)
    ctx.load_all_available_dialects()
    tpu.register_dialect(ctx)
    bodies = set()
    for line in calls:
        at = line.index("backend_config=") + len("backend_config=")
        config, _ = json.JSONDecoder().raw_decode(line[at:])
        bodies.add(config["custom_call_config"]["body"])
    modules = []
    with ctx:
        ctx.allow_unregistered_dialects = True
        for body in sorted(bodies):
            module = ir.Module.parse(base64.b64decode(body))
            PassManager.parse(
                "builtin.module(mosaic-serde{serialize=false})").run(
                    module.operation)
            modules.append(module)
    return modules


def _branches(block) -> tuple[int, int]:
    """How deep `scf.if`s nest in `block`, and how many of them one pass
    through it tests at most (the larger branch of each counted)."""
    deep = tested = 0
    for op in block.operations:
        if op.operation.name == "scf.if":
            inner = [_branches(b) for r in op.regions for b in r.blocks]
            deep = max(deep, 1 + max(d for d, _ in inner))
            tested += 1 + max(t for _, t in inner)
    return deep, tested


def _expert_kernels(lines: list, held: int, calls: int) -> None:
    """`calls` kernels named `expert_tiles` (a layer's and the one in its
    overflow's loop), and in each the step that picks the next weight
    block out of `held` experts' leaves does so in a tree: its
    conditionals nest at most `ceil(log2 held) + 2` deep, and a step
    tests at most `ceil(log2 held) + 6` of them (the tree's and the
    step's own six: the fetch's guard, the token tile's two, the sum's
    three). The chain before it tested one a held expert at every step,
    70 at 64 held (PERF.md section 6, PR 40)."""
    kernels = [line for line in lines if "tpu_custom_call" in line
               and "expert_tiles" in line]
    assert len(kernels) == calls
    levels = (held - 1).bit_length()
    for module in _mosaic_modules(kernels):
        func = module.body.operations[0]
        loops = [op for op in func.regions[0].blocks[0].operations
                 if op.operation.name == "scf.for"]
        assert len(loops) == 1
        deep, tested = _branches(loops[0].regions[0].blocks[0])
        assert deep <= levels + 2 and tested <= levels + 6, (deep, tested)


def test_every_expert_leaf_is_read_once_outside_any_loop(step):
    model, _, compiled = step
    hlo = compiled.as_text()
    _expert_leaves_read_once(hlo, model.cfg.experts_held, [1], 7168, 2048)
    _expert_kernels(hlo.splitlines(), model.cfg.experts_held, 2)


# -- `laguna-stream` at `laguna-s-2.1-ep8`'s own size -------------------------

LAGUNA_ROWS, LAGUNA_FRAME = 769, 256


@pytest.fixture(scope="module")
def laguna_step(one_chip):
    """(model, state shapes, the ring step compiled for the described
    chip) at the benchmark configuration's `model_config` as it stands."""
    import json
    import os

    from sitewhere_tpu.models import build_model

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "laguna-s-2.1-ep8.json")) as fh:
        model = build_model("laguna-stream", **json.load(fh)["model_config"])
    state, compiled = _compile_step(model, LAGUNA_ROWS, LAGUNA_FRAME,
                                    one_chip, jnp.float32)
    return model, state, compiled


def _context_kernels(lines, frame, heads, table):
    """The `context_rows` calls among `lines` that read two tables of
    shape `table`: each hands back `[frame, heads, 128]` float32 and
    nothing else, so no table is its output or aliased to one."""
    calls = [line for line in lines if "tpu_custom_call" in line
             and "context_rows" in line and line.count(table) == 2]
    assert all(re.search(rf"= f32\[{frame},{heads},128\]\S* custom-call\(",
                         line) and "output_to_operand_aliasing" not in line
               for line in calls), calls
    return calls


def test_laguna_step_moves_no_context_leaf_and_fits_the_chip(laguna_step):
    """Ten window leaves, six of 512 positions and four of 768, a
    position's keys or values 1,024 lanes: none is copied, transposed,
    sliced or GATHERED, as a table or as the frame's rows. Each layer's
    attention is ONE kernel that takes the layer's two tables as they
    rest behind the append of the position's own entries
    (ops/context_kernel.py): nothing of `[256, 768, 1024]` or `[256,
    512, 1024]`, nor of their blocked views, exists in the step; each
    table rests row-major; the donated state comes back in its own
    buffers; and the step's arguments and scratch fit a v5e's 16 GiB
    with room."""
    from chip_smoke import _table_moves

    model, state, compiled = laguna_step
    hlo = compiled.as_text()
    lines = hlo.splitlines()
    assert _table_moves(hlo, LAGUNA_ROWS) == []
    # nothing of a table's length but the leaves themselves: no view of
    # a table in blocks of positions, which the gathers went through
    shapes = set(re.findall(rf"\w+\[{LAGUNA_ROWS}(?:,\d+)*\]", hlo))
    assert shapes == {
        f"bf16[{LAGUNA_ROWS},512,1024]", f"bf16[{LAGUNA_ROWS},768,1024]",
        f"bf16[{LAGUNA_ROWS},3072]", f"f32[{LAGUNA_ROWS}]",
        f"s32[{LAGUNA_ROWS}]"}, shapes
    # ...and nothing of a frame's gathered contexts
    assert not re.search(rf"\[{LAGUNA_FRAME},(?:\d+,)?(?:768|512|256),1024\]",
                         hlo)
    assert "mini-gather" not in hlo
    whiles = [line for line in lines if " while(" in line]
    assert all("moe_experts" in line for line in whiles)
    # one kernel a layer: two over the full layers' tables with 48 heads,
    # three over the sliding layers' with 72 (80 rows: whole tiles)
    full = _context_kernels(lines, LAGUNA_FRAME, 48,
                            f"bf16[{LAGUNA_ROWS},768,1024]")
    sliding = _context_kernels(lines, LAGUNA_FRAME, 80,
                               f"bf16[{LAGUNA_ROWS},512,1024]")
    assert (len(full), len(sliding)) == (2, 3)
    assert all("attn_full" in line for line in full)
    assert all("attn_window" in line for line in sliding)
    assert len([line for line in lines if "tpu_custom_call" in line
                and "context_rows" in line]) == model.layers == 5
    for positions, leaves in ((512, 6), (768, 4)):
        table = f"bf16[{LAGUNA_ROWS},{positions},1024]"
        assert sum(leaf.shape == (LAGUNA_ROWS, positions, 1024)
                   for leaf in state.values()) == leaves
        layouts = set(re.findall(re.escape(table) + r"\{([\d,]+)", hlo))
        assert layouts == {"2,1,0"}
        # a table is written by the append of a row's one entry and by
        # nothing else
        scatters = [line for line in lines if " scatter(" in line
                    and f"= {table}" in line]
        assert len(scatters) == leaves
        assert all("unique_indices=true" in line for line in scatters)
    moved = [line for line in lines if re.search(
        r"= bf16\[\d+,(?:512|768|256),1024\]\S* "
        r"(?:copy|transpose|slice|dynamic-slice|gather)\(", line)]
    assert moved == []
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes
    assert state_bytes > 9.6e9
    # the scratch was the gathered contexts': `temp_size_in_bytes` read
    # 620,203,520 on PR 37's tree and reads 100,768,768 here (PERF.md
    # section 6, PR 38)
    assert mem.temp_size_in_bytes < 0.15e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.7e9


def test_laguna_expert_leaves_are_read_once_by_one_kernel_a_layer(
        laguna_step):
    """The expert kernel at its second width (3072 x 1024, 32 held, 96
    leaves a layer, four layers): the same grouped pass, from the shapes
    it is handed."""
    model, _, compiled = laguna_step
    hlo = compiled.as_text()
    _expert_leaves_read_once(hlo, model.experts.held, [1, 2, 3, 4], 3072,
                             1024)
    _expert_kernels(hlo.splitlines(), model.experts.held, 8)


def test_lstm_stream_step_moves_rows_of_one_table(one_chip):
    """`lstm-stream` at `stream-512k`'s size: the compiled step takes one
    sorted gather from ONE table and writes it with one scatter, in
    place; no leaf of a scalar a row is left, each of which cost a
    gather and a scatter of its own over the whole fleet (PERF.md
    section 6, PR 31); the table is neither copied nor transposed and
    comes back in its own buffer."""
    from chip_smoke import _table_moves
    from sitewhere_tpu.models import build_model

    model = build_model("lstm-stream", window=64, hidden=64)
    state, compiled = _compile_step(model, FLEET_ROWS, FRAME, one_chip,
                                    jnp.float16)
    hlo = compiled.as_text()
    assert _table_moves(hlo, FLEET_ROWS) == []
    (leaf,) = jax.tree.leaves(state)
    assert leaf.shape == (FLEET_ROWS, 2, 128)
    shapes = set(re.findall(rf"\w+\[(?:\d+,)*{FLEET_ROWS}(?:,\d+)*\]", hlo))
    assert shapes == {f"f32[{FLEET_ROWS},2,128]"}, shapes
    # at rest one row a tile, 1 KB contiguous
    assert f"f32[{FLEET_ROWS},2,128]{{2,1,0:T(2,128)}} parameter" in hlo
    lines = hlo.splitlines()
    gathers = [line for line in lines if " gather(" in line]
    assert len(gathers) == 1 and f"= f32[{FRAME},2,128]" in gathers[0]
    assert "indices_are_sorted=true" in gathers[0]
    scatters = [line for line in lines if " scatter(" in line]
    assert len(scatters) == 1 and f"f32[{FLEET_ROWS},2,128]" in scatters[0]
    assert "unique_indices=true" in scatters[0]
    assert "indices_are_sorted=true" not in scatters[0]
    assert "tpu_custom_call" not in hlo
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= leaf.size * leaf.dtype.itemsize


# -- `olmo-hybrid-stream` at `olmo-hybrid-7b-pp8`'s own size --------------------

OLMO_ROWS, OLMO_FRAME = 769, 256


@pytest.fixture(scope="module")
def olmo_step(one_chip):
    """(model, state shapes, the ring step compiled for the described
    chip) at the benchmark configuration's `model_config` as it stands."""
    import json
    import os

    from sitewhere_tpu.models import build_model

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "olmo-hybrid-7b-pp8.json")) as fh:
        model = build_model("olmo-hybrid-stream",
                            **json.load(fh)["model_config"])
    state, compiled = _compile_step(model, OLMO_ROWS, OLMO_FRAME, one_chip,
                                    jnp.float32)
    return model, state, compiled


def test_olmo_step_moves_no_state_table_and_holds_a_layers_rows_at_a_time(
        olmo_step):
    """Three matrix states of 2.2 MB a row (`f32[769, 15, 96, 384]`: two
    heads of 192 values side by side in three lane tiles), three leaves
    of conv taps and two context leaves of 2.9 MB a row: none is copied,
    transposed or sliced, as a table or as the frame's gathered rows;
    each rests row-major. A matrix state is never gathered or scattered
    at all: ONE kernel a linear layer takes the table and hands it back
    aliased (ops/state_kernel.py), and nothing of a frame's rows of it,
    `f32[256, 15, 96, 384]`, exists in the step. Nor is a context: the
    full layer's attention is ONE kernel that takes the two tables as
    they rest behind the append of the position's own entries
    (ops/context_kernel.py), and nothing of `[256, 384, 3840]`, nor of
    its blocked view, exists. The taps' rows are gathered and scattered
    by ONE gather and ONE scatter a leaf, no loop; the donated state
    comes back in its own buffers."""
    from chip_smoke import _table_moves

    model, state, compiled = olmo_step
    hlo = compiled.as_text()
    lines = hlo.splitlines()
    assert _table_moves(hlo, OLMO_ROWS) == []
    table = f"f32[{OLMO_ROWS},15,96,384]"
    context = f"bf16[{OLMO_ROWS},384,3840]"
    shapes = set(re.findall(rf"\w+\[{OLMO_ROWS}(?:,\d+)*\]", hlo))
    assert shapes == {
        table, f"bf16[{OLMO_ROWS},270,128]", context,
        f"bf16[{OLMO_ROWS},3840]", f"f32[{OLMO_ROWS}]",
        f"s32[{OLMO_ROWS}]"}, shapes
    assert not re.search(rf"\[{OLMO_FRAME},(?:\d+,)?(?:384|64),3840\]", hlo)
    assert "mini-gather" not in hlo
    assert not [line for line in lines if " while(" in line]
    # the full layer's one kernel reads both context tables and returns
    # 30 heads' outputs (32 rows: whole tiles)
    (attends,) = _context_kernels(lines, OLMO_FRAME, 32, context)
    assert "attn_full" in attends
    # the three kernels of the linear layers: each takes a state table
    # and returns it in the same buffer; a table is a parameter, a
    # kernel's first result or the step's result, and nothing else
    kernels = [line for line in lines if "tpu_custom_call" in line
               and line != attends]
    assert len(kernels) == 3
    assert all(re.search(rf"= \({re.escape(table)}\S*, f32\[{OLMO_FRAME},16,"
                         rf"384\]", line)
               and "output_to_operand_aliasing={{0}: (1, {})}" in line
               and "gdn_state" in line for line in kernels)
    assert len({re.search(r"custom-call\(%\S+, (%state__s\d__\S*),",
                          line).group(1) for line in kernels}) == 3
    of_a_table = [line for line in lines if re.match(
        rf"\s*(?:ROOT )?%\S+ = {re.escape(table)}", line)]
    assert len(of_a_table) == 6 and all(
        " parameter(" in line or " get-tuple-element(" in line
        for line in of_a_table), of_a_table
    assert f"f32[{OLMO_FRAME},15,96,384]" not in hlo
    # the kernel's delta rule: a row whole, one block, four vectors a row
    (module,) = _mosaic_modules(kernels)
    text = module.operation.get_asm(enable_debug_info=False)
    assert "memref<1x4x15x384xf32" in text and "memref<1x15x96x384xf32" in text
    for shape, leaves, scattered in ((table, 3, 0),
                                     (f"bf16[{OLMO_ROWS},384,3840]", 2, 2),
                                     (f"bf16[{OLMO_ROWS},270,128]", 3, 3)):
        dims = tuple(int(d) for d in shape[shape.index("[") + 1:-1].split(","))
        assert sum(x.shape == dims for x in state.values()) == leaves
        layouts = set(re.findall(re.escape(shape) + r"\{([\d,]+)", hlo))
        assert layouts == {",".join(map(str, reversed(range(len(dims)))))}, \
            shape
        scatters = [line for line in lines if " scatter(" in line
                    and f"= {shape}" in line]
        assert len(scatters) == scattered, shape
        assert all("unique_indices=true" in line
                   and "indices_are_sorted=true" not in line
                   for line in scatters)
    moved = [line for line in lines if re.search(
        r"= (?:f32\[\d+,15,96,384\]|f32\[\d+,3,96,384\]|"
        r"bf16\[\d+,384,3840\]|bf16\[\d+,64,3840\])\S* "
        r"(?:copy|transpose|slice|dynamic-slice|gather|scatter)\(", line)
        # (inside a scatter's fusion the updates pass a `transpose` that
        # permutes nothing)
        and "dimensions={0,1,2,3}" not in line
        # (a context table is written by the append of a row's one entry)
        and not re.search(rf"= {re.escape(context)}\S* scatter\(", line)]
    assert moved == []
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes > 9.8e9
    assert mem.argument_size_in_bytes > 13.0e9
    # the scratch's peak was the full layer's two gathered contexts (1.51
    # GB; PERF.md section 6, PR 36): `temp_size_in_bytes` read
    # 2,352,224,256 on PR 37's tree and reads 79,385,088 here (PR 38)
    assert mem.temp_size_in_bytes < 0.12e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.2e9


# -- `lfm2-stream` at `lfm2-24b-a2b-pp5`'s own size -----------------------------

LFM2_ROWS, LFM2_FRAME = 2561, 512


@pytest.fixture(scope="module")
def lfm2_step(one_chip):
    """(model, state shapes, the ring step compiled for the described
    chip) at the benchmark configuration's `model_config` as it stands."""
    import json
    import os

    from sitewhere_tpu.models import build_model

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "lfm2-24b-a2b-pp5.json")) as fh:
        model = build_model("lfm2-stream", **json.load(fh)["model_config"])
    state, compiled = _compile_step(model, LFM2_ROWS, LFM2_FRAME, one_chip,
                                    jnp.float32)
    return model, state, compiled


def test_lfm2_step_streams_every_expert_once_and_moves_no_table(lfm2_step):
    """Six expert layers of 64 held experts of 2048 x 1536: ONE
    `expert_tiles` call a layer takes the layer's 192 leaves as they
    rest, outside any loop (the six `while`s are the overflow's, one a
    layer where there were 64: four fifths of this step's compile), and
    no leaf is copied or sliced. Six conv
    tables of 8 KB a row are gathered once and scattered once each, not
    copied, sliced or transposed; the tied embedding is gathered from
    and contracted over as it rests. The two attention layers' four
    context tables are read where they rest: a key-value head of 64 is
    half a lane tile, which `ops/context_kernel.py` takes two to a tile,
    so each layer's attention is ONE `context_rows` call over its two
    tables behind the append of the position's own entries, and no
    table's rows are gathered (the four gathers of 268 MB each were
    3.28 ms of a 20.1 ms step on a v5e; PERF.md section 6)."""
    from chip_smoke import _table_moves
    from sitewhere_tpu.ops import context_kernel, expert_kernel

    model, state, compiled = lfm2_step
    hlo = compiled.as_text()
    lines = hlo.splitlines()
    assert expert_kernel.fits(LFM2_FRAME, 2048, 1536, 128)
    assert context_kernel.fits_paired((LFM2_ROWS, 512, 512), jnp.bfloat16,
                                      32, 8)
    assert _table_moves(hlo, LFM2_ROWS) == []
    conv, context = f"bf16[{LFM2_ROWS},32,128]", f"bf16[{LFM2_ROWS},512,512]"
    shapes = set(re.findall(rf"\w+\[{LFM2_ROWS}(?:,\d+)*\]", hlo))
    assert shapes == {conv, context, f"bf16[{LFM2_ROWS},2048]",
                      f"f32[{LFM2_ROWS}]", f"s32[{LFM2_ROWS}]"}, shapes
    _expert_leaves_read_once(hlo, model.experts.held, [2, 3, 4, 5, 6, 7],
                             2048, 1536)
    assert model.experts.held == 64
    # an expert layer's call and the one inside its overflow's loop, and
    # an attention layer's one call over its two tables (32 heads of 64
    # handed back in whole lane tiles): no other kernel
    attends = _context_kernels(lines, LFM2_FRAME, 32, context)
    assert len(attends) == 2 and all("attn_full" in line for line in attends)
    kernels = [line for line in lines if "tpu_custom_call" in line
               and line not in attends]
    assert len(kernels) == 2 * 6 and all("expert_tiles" in line
                                         for line in kernels)
    _expert_kernels(lines, model.experts.held, 2 * 6)
    assert len([line for line in lines if " while(" in line]) == 6
    for shape, leaves in ((conv, 6), (context, 4)):
        dims = tuple(int(d) for d in shape[shape.index("[") + 1:-1].split(","))
        assert sum(x.shape == dims for x in state.values()) == leaves
        layouts = set(re.findall(re.escape(shape) + r"\{([\d,]+)", hlo))
        assert layouts == {"2,1,0"}, shape
        scatters = [line for line in lines if " scatter(" in line
                    and f"= {shape}" in line]
        assert len(scatters) == leaves, shape
        assert all("unique_indices=true" in line
                   and "indices_are_sorted=true" not in line
                   for line in scatters)
    # nothing of a frame's gathered contexts: no table's rows are gathered
    assert not re.search(rf"\[{LFM2_FRAME},(?:\d+,)?512,512\]", hlo)
    assert not [line for line in lines if " gather(" in line
                and context in line]
    assert len([line for line in lines if re.search(
        rf"= bf16\[{LFM2_FRAME},32,128\]\S* gather\(", line)]) == 6
    moved = [line for line in lines if re.search(
        r"= (?:bf16\[\d+,32,128\]|bf16\[65536,2048\]|bf16\[2048,65536\])"
        r"\S* (?:copy|transpose|slice|dynamic-slice)\(", line)
        # (round a gather or inside a scatter's fusion the rows pass a
        # `transpose` that permutes nothing)
        and "dimensions={0,1,2}" not in line]
    assert moved == []
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes > 5.5e9
    # 8.05 GB of weights and 5.51 of state; the scratch was the two
    # attention layers' gathered contexts: `temp_size_in_bytes` read
    # 1,088,491,520 while they were gathered and reads 162,520,064 here
    assert 13.5e9 < mem.argument_size_in_bytes < 13.6e9
    assert mem.temp_size_in_bytes < 0.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.8e9


# -- `ouro-stream` at `ouro-2.6b-pp4`'s own size --------------------------------

OURO_ROWS, OURO_FRAME = 65, 16


@pytest.fixture(scope="module")
def ouro_step(one_chip):
    """(model, state shapes, the ring step compiled for the described
    chip) at the benchmark configuration's `model_config` as it stands."""
    import json
    import os

    from sitewhere_tpu.models import build_model

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "ouro-2.6b-pp4.json")) as fh:
        model = build_model("ouro-stream", **json.load(fh)["model_config"])
    state, compiled = _compile_step(model, OURO_ROWS, OURO_FRAME, one_chip,
                                    jnp.float32)
    return model, state, compiled


def test_ouro_step_is_one_pass_body_that_moves_no_table_and_no_weight(
        ouro_step):
    """Four passes of twelve layers are ONE compiled layer body: the
    passes a `while`, the layers a `while` inside it over weights stacked
    `[12, ...]`, and in it ONE `context_rows` call site that reads a
    2,048-lane block of the two tables `[65, 448, 98304]` as they rest,
    the position's own entry beside them (each of the 48 contexts a
    block; the step appends a row's 48 entries as one line when the
    passes end, in place). No table is copied, transposed, sliced or
    gathered, nothing
    of a frame's gathered contexts exists, and each rests row-major. No
    slice of a stacked weight is made before its product: it is read in
    the product's own fusion, so the passes stream each weight once a
    pass and no more. The donated state comes back in its own buffers;
    arguments and scratch fit the chip."""
    from chip_smoke import _table_moves

    model, state, compiled = ouro_step
    hlo = compiled.as_text()
    lines = hlo.splitlines()
    assert _table_moves(hlo, OURO_ROWS) == []
    table = f"bf16[{OURO_ROWS},448,98304]"
    shapes = set(re.findall(rf"\w+\[{OURO_ROWS}(?:,\d+)*\]", hlo))
    assert shapes == {table, f"bf16[{OURO_ROWS},2048]", f"f32[{OURO_ROWS}]",
                      f"s32[{OURO_ROWS}]"}, shapes
    assert not re.search(rf"\[{OURO_FRAME},448,(?:2048|98304)\]", hlo)
    assert "mini-gather" not in hlo
    (call,) = _context_kernels(lines, OURO_FRAME, 16, table)
    assert "attn_full" in call and "loop_pass" in call
    assert len([line for line in lines if "tpu_custom_call" in line]) == 1
    whiles = [line for line in lines if " while(" in line]
    assert len(whiles) == 4
    # the passes, the layers inside them, and the two appends' loops of
    # row updates, once a step: a row's 48 entries go in as one line
    # (an append an entry, 96 such loops, was 6.6 of an 18.3 ms step)
    appends = [line for line in whiles if "ctx_append" in line]
    assert len(appends) == 2 and not any("loop_pass" in line
                                         for line in appends)
    layouts = set(re.findall(re.escape(table) + r"\{([\d,]+)", hlo))
    assert layouts == {"2,1,0"}
    # a table is a parameter, a loop's carry, or the append's update of
    # a row's block in place, and nothing else
    made = {re.search(rf"= {re.escape(table)}\S* ([\w-]+)\(", line).group(1)
            for line in lines if re.search(rf"= {re.escape(table)}\S* ", line)}
    assert made <= {"parameter", "get-tuple-element", "dynamic-update-slice",
                    "while", "tuple", "bitcast"}, made
    # the stacked weights: each a parameter of the step and of the
    # loops, and read by a product's fusion at the layer's index
    comps, entry = _computations(hlo)
    fused = {c for lines_ in comps.values() for line in lines_
             if " fusion(" in line
             for c in re.findall(r"calls=(%[\w.\-]+)", line)}
    weights = r"bf16\[(?:1,)?(?:2048,2048|2048,5632|5632,2048)\]"
    outside = [line for name, body in comps.items() if name not in fused
               for line in body]
    # nothing of a layer's weight is made outside a product's fusion
    assert not [line for line in outside
                if re.search(rf"= {weights}\S* ", line)]
    stacked = r"bf16\[12,(?:2048,2048|2048,5632|5632,2048)\]"
    names = {m.group(1) for line in outside
             for m in [re.match(rf"\s*(?:ROOT )?(%\S+) = {stacked}", line)]
             if m}
    uses = [line for line in outside if " = " in line and any(
        re.search(re.escape(n) + r"[,)]", line.split(" = ", 1)[1])
        for n in names)]
    # a product's fusion reads it at the layer's index (the MLP's gate
    # and up in one), and the rest hands it on
    reads = [line for line in uses if " fusion(" in line]
    assert len(reads) >= 6 and all("kind=kOutput" in line
                                   for line in reads), reads
    assert all(re.search(r" (?:tuple|while|get-tuple-element|bitcast)\(",
                         line) for line in uses if line not in reads), uses
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes > 11.4e9
    # 11.45 GB of contexts and 1.636 GB of weights; the scratch is a
    # layer's activations and the logits (1,776,128 here; 203,360,768
    # while the rotary turn reshaped q and k into heads and the compiler
    # transposed their stacked projections to make that a view)
    assert 13.0e9 < mem.argument_size_in_bytes < 13.2e9
    assert mem.temp_size_in_bytes < 0.02e9


# -- `nemotron-h-stream` at `nemotron-3-super-ep8`'s own size --------------------

NEMOTRON_ROWS, NEMOTRON_FRAME = 385, 128


@pytest.fixture(scope="module")
def nemotron_step(one_chip):
    """(model, state shapes, the ring step compiled for the described
    chip) at the benchmark configuration's `model_config` as it stands."""
    import json
    import os

    from sitewhere_tpu.models import build_model

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "nemotron-3-super-ep8.json")) as fh:
        model = build_model("nemotron-h-stream",
                            **json.load(fh)["model_config"])
    state, compiled = _compile_step(model, NEMOTRON_ROWS, NEMOTRON_FRAME,
                                    one_chip, jnp.float32)
    return model, state, compiled


def test_nemotron_step_lowers_both_kernels_and_moves_no_table(nemotron_step):
    """Five Mamba-2 states of 4 MiB a row (`f32[385, 64, 128, 128]`: two
    heads of 64 to a row of lanes over a state of 128), five leaves of
    conv taps, one attention layer's two context tables and five expert
    layers of 64 held latent experts of 1024 x 2688. Each Mamba-2 layer
    is ONE `state_rows` call (ops/state_kernel.py's decay-and-write rule,
    three vectors a row, the row in two blocks) that takes its table and
    hands it back in the same buffer, and nothing of a frame's rows of
    it exists; each expert layer is ONE `expert_tiles` call of the
    two-leaf form (and the one in its overflow's loop) that takes its
    128 leaves as they rest; the attention layer is ONE `context_rows`
    call over its two tables. No table is copied, transposed or sliced,
    and the step's scratch is small beside the 13.9 GB it is handed."""
    from chip_smoke import _table_moves
    from sitewhere_tpu.ops import context_kernel, expert_kernel, state_kernel

    model, state, compiled = nemotron_step
    hlo = compiled.as_text()
    lines = hlo.splitlines()
    table = f"f32[{NEMOTRON_ROWS},64,128,128]"
    context = f"bf16[{NEMOTRON_ROWS},512,256]"
    assert state_kernel.blocks((NEMOTRON_ROWS, 64, 128, 128)) == 2
    assert expert_kernel.fits(NEMOTRON_FRAME, 1024, 2688, 128, 2)
    assert context_kernel.fits((NEMOTRON_ROWS, 512, 256), jnp.bfloat16, 32, 2)
    assert _table_moves(hlo, NEMOTRON_ROWS) == []
    shapes = set(re.findall(rf"\w+\[{NEMOTRON_ROWS}(?:,\d+)*\]", hlo))
    assert shapes == {table, f"bf16[{NEMOTRON_ROWS},240,128]", context,
                      f"bf16[{NEMOTRON_ROWS},4096]", f"f32[{NEMOTRON_ROWS}]",
                      f"s32[{NEMOTRON_ROWS}]"}, shapes
    states = [line for line in lines if "tpu_custom_call" in line
              and "state_rows" in line]
    assert len(states) == 5
    assert all(re.search(rf"= \({re.escape(table)}\S*, f32\["
                         rf"{2 * NEMOTRON_FRAME},33,128\]", line)
               and "output_to_operand_aliasing={{0}: (1, {})}" in line
               and "ssm_state" in line for line in states), states
    assert len({re.search(r"custom-call\(%\S+, (%state__s\d__\S*),",
                          line).group(1) for line in states}) == 5
    assert f"f32[{NEMOTRON_FRAME},64,128,128]" not in hlo
    of_a_table = [line for line in lines if re.match(
        rf"\s*(?:ROOT )?%\S+ = {re.escape(table)}", line)]
    assert all(" parameter(" in line or " get-tuple-element(" in line
               for line in of_a_table), of_a_table
    # the kernel's decay-and-write rule: three vectors of 32 rows of
    # lanes a block
    (module,) = _mosaic_modules(states)
    text = module.operation.get_asm(enable_debug_info=False)
    assert "memref<1x3x32x128xf32" in text and "memref<1x32x128x128xf32" in text
    (attends,) = _context_kernels(lines, NEMOTRON_FRAME, 32, context)
    assert "attn_full" in attends
    _expert_leaves_read_once(hlo, 64, [1, 3, 5, 8, 10], 1024, 2688,
                             names=("up", "down"))
    _expert_kernels(lines, 64, 2 * 5)
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes > 8.3e9
    # 5.53 GB of weights and 8.40 of state
    assert 13.9e9 < mem.argument_size_in_bytes < 14.0e9
    assert mem.temp_size_in_bytes < 0.2e9


# -- `context_rows` alone, at the served leaves' shapes --------------------------

# (the tables' shape, the frame, query heads, key-value heads, a head's
# width, whether a row holds contexts side by side of which one block is
# read beside the own entry): each model's two-table call in its step
TWO_TABLES = {
    "ouro-2.6b-pp4": ((OURO_ROWS, 448, 98304), OURO_FRAME, 16, 16, 128,
                      True),
    "olmo-hybrid-7b-pp8": ((OLMO_ROWS, 384, 3840), OLMO_FRAME, 30, 30, 128,
                           False),
    "lfm2-24b-a2b-pp5": ((LFM2_ROWS, 512, 512), LFM2_FRAME, 32, 8, 64,
                         False),
    "laguna-s-2.1-ep8.full": ((LAGUNA_ROWS, 768, 1024), LAGUNA_FRAME, 48, 8,
                              128, False),
    "laguna-s-2.1-ep8.sliding": ((LAGUNA_ROWS, 512, 1024), LAGUNA_FRAME, 72,
                                 8, 128, False),
}


def _kernel_call(fn, args: list) -> tuple:
    """`fn` over `args` (shapes) compiled for the described chip: its one
    kernel's HLO line and backend config."""
    import json

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    (line,) = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    at = line.index("backend_config=") + len("backend_config=")
    return line, json.JSONDecoder().raw_decode(line[at:])[0]


def _op_counts(op, counts=None) -> dict:
    """How many operations of each name `op` holds, at any depth."""
    counts = {} if counts is None else counts
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                name = inner.operation.name
                counts[name] = counts.get(name, 0) + 1
                _op_counts(inner, counts)
    return counts


@pytest.mark.parametrize("leaf", TWO_TABLES)
def test_context_rows_copies_a_rows_prefix_in_the_rows_own_grid_step(
        leaf, one_chip):
    """The two-table form at a served leaf's shape, compiled for the
    described chip: ONE grid axis, the frame's rows; the two tables stay
    where they rest (`any`) and a row's prefix comes into two double
    buffers of a whole row; the copies are started in two trees with one
    branch a length of prefix (the first row's, and the next row's while
    a row computes) and waited on in one: one copy of each table a length,
    so two a row, and no loop of a copy a position block; and what the
    call uses of VMEM stays within `vmem_bytes`, which it asks for."""
    from sitewhere_tpu.ops import context_kernel

    shape, frame, heads, kv, d, blocked = TWO_TABLES[leaf]
    width = kv * d

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [described(shape, jnp.bfloat16)] * 2 + [
        described((frame,), jnp.int32)] * 2 + [
        described((frame, heads, d), jnp.float32)]
    if blocked:
        args += [described((), jnp.int32),
                 (described((frame, width), jnp.bfloat16),) * 2]
    line, config = _kernel_call(lambda *a: context_kernel.context_rows(
        *a, kv=kv, scale=d ** -0.5), args)
    (module,) = _mosaic_modules([line])
    func = module.body.operations[0]
    assert str(func.attributes["iteration_bounds"]) == f"array<i64: {frame}>"
    kinds = str(func.attributes["function_type"])
    table = "x".join(map(str, shape))
    assert kinds.count(f"memref<{table}xbf16, #tpu.memory_space<any>>") == 2
    assert kinds.count(f"memref<2x{shape[1]}x{width}xbf16, "
                       "#tpu.memory_space<vmem>>") == 2
    lengths = shape[1] // context_kernel.position_block(shape[1])
    assert 6 <= lengths <= context_kernel.MAX_BLOCKS
    ops = _op_counts(func)
    assert ops["tpu.enqueue_dma"] == 2 * 2 * lengths
    assert ops["tpu.wait_dma2"] == 2 * lengths
    assert "scf.for" not in ops and "scf.while" not in ops
    (asked,), (used,) = (config[key] for key in (
        "scoped_memory_configs", "used_scoped_memory_configs"))
    assert int(used["size"]) <= int(asked["size"]) == \
        context_kernel.vmem_bytes(shape, heads, kv, width)


def test_the_one_table_form_is_left_as_it_lowered(one_chip):
    """`context_rows`' one-table form at `deepseek-v3-ep16`'s shape (four
    blocks of 1,024 rows of `[192, 640]` and the scratch row, 128 heads,
    512 lanes of values): its Mosaic module, printed without source
    locations, hashes to the text it lowered to while the two-table form
    still copied whole rows, so that form's prefixes leave it alone."""
    import hashlib

    from sitewhere_tpu.ops import context_kernel

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    frame = 1024
    line, _ = _kernel_call(lambda t, d, p, q: context_kernel.context_rows(
        t, None, d, p, q, scale=0.1, value_width=512), [
        described((4097, 192, 640), jnp.bfloat16),
        described((frame,), jnp.int32), described((frame,), jnp.int32),
        described((frame, 128, 640), jnp.bfloat16)])
    (module,) = _mosaic_modules([line])
    text = module.operation.get_asm(enable_debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "de51685004caafe60d06205d87d8de89dba14f7843fca1c326c2e059937f878c")
