"""TPU kernels (Pallas) for hot ops the XLA autofuser leaves on the
table. Each kernel ships with a pure-jax reference path and an
auto-selection helper; CPU/test runs always take the reference path
(Pallas interpret mode is exercised by dedicated parity tests).
`expert_kernel` (a layer's held experts of `dsv3-stream`, of
`laguna-stream` and of `lfm2-stream`, SiLU-gated, and of
`nemotron-h-stream`, `relu` squared) is imported by its one caller,
models/seqblocks.py,
which holds its plain twin. `state_kernel` (a layer's matrix states,
updated in the rows of the ring's table they rest in: the gated delta
rule of `olmo-hybrid-stream` and Mamba-2's decay and write of
`nemotron-h-stream`) is imported by models/olmo_hybrid.py and
models/nemotron_h.py, whose `_gdn_cell` and `_ssm_cell` are its plain
twins: the kernel is handed to `RowsInTurn.update`
(scoring/stream.py), which promises when it runs; the kernel promises
that rows the frame does not name, the scratch row among them, come
back as they were, and that every write has landed when it returns.
`context_kernel` (a layer's attention of `laguna-stream`, of
`olmo-hybrid-stream` and of `lfm2-stream` over each device's stored keys
and values, read in the rows of the ring's table they rest in; a
key-value head of 64, half a lane tile, is read two to a tile) is
imported by models/seqblocks.py, whose `_decode_rows` is its plain twin:
the ring hands the two tables over as `ContextAtRest`s
(scoring/stream.py), the position's own entries are appended first, and
the kernel only reads. Its one-table form reads `dsv3-stream`'s latent
context, one table that is both keys and values, for
models/dsv3.py, whose `_attend_decode` is that form's plain twin."""

from sitewhere_tpu.ops.lstm_kernel import (  # noqa: F401
    lstm_window_final,
    pallas_ok,
)
