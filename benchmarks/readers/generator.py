"""How late the load generator ran: a quantile of sent minus due over the
window's ticks, so that a starved generator is not read as a fast server."""

import numpy as np


def read(obs, q: float):
    due, sent = obs["report"]["due"], obs["report"]["sent"]
    if not due:
        return None
    late = (np.asarray(sent) - np.asarray(due)) * 1e3
    return float(np.percentile(late, 100 * q))
