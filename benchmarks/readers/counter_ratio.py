"""One count of the window over another. A term is a counter of the
program (`{"counter": name}`) or a count of the harness's own
(`{"window": "events_in_window"}`)."""


def _term(obs, spec: dict):
    if "counter" in spec:
        return obs["window_metrics"]["counters"].get(spec["counter"])
    return obs.get(spec["window"])


def read(obs, numerator: dict, denominator: dict):
    num, den = _term(obs, numerator), _term(obs, denominator)
    if num is None or not den:
        return None
    return num / den
