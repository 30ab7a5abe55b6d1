"""TPU kernels (Pallas) for hot ops the XLA autofuser leaves on the
table. Each kernel ships with a pure-jax reference path and an
auto-selection helper; CPU/test runs always take the reference path
(Pallas interpret mode is exercised by dedicated parity tests).
`expert_kernel` (a layer's held experts of `dsv3-stream` and of
`laguna-stream`) is imported by its one caller, models/seqblocks.py,
which holds its plain twin."""

from sitewhere_tpu.ops.lstm_kernel import (  # noqa: F401
    lstm_window_final,
    pallas_ok,
)
