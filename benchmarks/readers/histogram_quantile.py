"""A quantile of one of the program's histograms over the window only."""

import math


def read(obs, histogram: str, q: float, scale: float = 1.0):
    h = obs["window_metrics"]["histograms"].get(histogram)
    if h is None:
        return None
    counts, edges = h["counts"], h["buckets"]
    total = sum(counts)
    if total <= 0:
        return None
    # linear interpolation inside the bucket that crosses the rank, as
    # the program's own Histogram.quantile does; the overflow bucket is
    # bounded by the largest value seen
    target = max(math.ceil(q * total), 1)
    seen = 0
    for i, c in enumerate(counts):
        if c and seen + c >= target:
            hi = edges[i] if i < len(edges) else h["max"]
            lo = edges[i - 1] if 0 < i <= len(edges) else 0.0
            return (lo + (target - seen) / c * (hi - lo)) * scale
        seen += c
    return h["max"] * scale
