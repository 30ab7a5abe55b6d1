"""One count of the window over another (readers/counter_ratio.py, whose
terms these are), times `scale`: bytes a step as MB a step."""

from benchmarks.readers import counter_ratio


def read(obs, numerator: dict, denominator: dict, scale: float):
    ratio = counter_ratio.read(obs, numerator, denominator)
    return None if ratio is None else ratio * scale
