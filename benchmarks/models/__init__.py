"""One file a model the program serves, found by the configuration's
`model` key (`lstm-stream` -> `lstm_stream.py`). Each holds the model's
plain reference and its counts:

    tenant_params(seed, tenant, model_config) -> weights (a pytree, laid
        out as the program's checkpoints), made on the device from the seed
    run(params, hist, frames, fed, model_config, compute_dtype) -> scores
        [T, D] for ticks [T, D] after seeding from hist [D, >=W]; `fed`
        [T, D] marks the events the program was given
    flops_per_event(model_config), bytes_per_event(model_config, score_dtype)

A file here imports nothing of the program.
"""

import importlib


def load(model: str):
    return importlib.import_module(
        f"benchmarks.models.{model.replace('-', '_')}")
