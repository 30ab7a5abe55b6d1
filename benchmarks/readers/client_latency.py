"""A quantile of the client's per-frame latency (due to seen published):
the tails, which a 20 s window cannot hold to a bound (PERF.md, section
2), and the saturated cell's latency, which decides nothing there."""

import numpy as np


def read(obs, q: float):
    latency = obs.get("latency_ms")
    if latency is None or not len(latency):
        return None
    return float(np.percentile(latency, 100 * q))
