"""The comparison that decides `correct`: served scores against the plain
reference's, alert decisions, and each number beside its limit. The same
for every model; what a model computes is its own file's, under
benchmarks/models/.
"""

from __future__ import annotations

import numpy as np

# the nearest precision below the one a configuration states: what the
# control of `correct` computes in (PERF.md, section 2)
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def score_gaps(served: np.ndarray, ref: np.ndarray) -> tuple[float, float]:
    """(widest, mean) gap between served and reference scores, each gap
    measured against the reference score or 1, whichever is larger: a
    score is z-like, so small ones are compared absolutely."""
    if served.size == 0:
        return float("inf"), float("inf")
    gap = np.abs(served.astype(np.float64) - ref) / np.maximum(np.abs(ref), 1.0)
    return float(gap.max()), float(gap.mean())


def alert_mismatches(flagged: np.ndarray, ref: np.ndarray, threshold: float,
                     margin: float) -> int:
    """Events whose alert decision differs from the reference's, leaving
    out those whose reference score lies within `margin` (relative) of
    the threshold, where rounding alone decides."""
    band = margin * max(threshold, 1.0)
    sure_hi = ref >= threshold + band
    sure_lo = ref < threshold - band
    return int((sure_hi & ~flagged).sum() + (sure_lo & flagged).sum())


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """`correct` and, for the result line, each number beside its limit."""
    checks = {name: {"value": numbers[name], "limit": limits[name]}
              for name in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
