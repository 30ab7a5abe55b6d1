"""Quantities of the traced slice (benchmarks/xplane.py reduces it).

    step_device_us  device-busy time of the slice over its dispatches
    step_roofline   the least time the chip could take for the events the
                    slice's steps scored (the model's counts, benchmarks/models/), over the
                    time those steps ran on it, in %
    step_mfu        FLOPs those events need over slice seconds x chips x
                    the bf16 peak, in %
    device_idle     1 - busy over the slice, in %
"""



def least_seconds(events: float, flops_per_event: float,
                  bytes_per_event: float, peaks: dict) -> tuple[float, str]:
    """The roofline: the least time the chip could take for `events`, and
    which of the two peaks sets it."""
    t_flops = events * flops_per_event / peaks["bf16_flops_per_s"]
    t_bytes = events * bytes_per_event / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def read(obs, quantity: str):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if not trace or not trace["busy_s"]:
        return None
    if quantity == "device_idle":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if not trace["steps"]:
        return None
    if quantity == "step_device_us":
        return 1e6 * trace["busy_s"] / trace["steps"]
    if not peaks or not trace["events"]:
        return None
    if quantity == "step_roofline":
        least, _bound = least_seconds(
            trace["events"], obs["flops_per_event"], obs["bytes_per_event"],
            peaks)
        return 100.0 * least / trace["step_s"]
    if quantity == "step_mfu":
        return 100.0 * trace["events"] * obs["flops_per_event"] / (
            trace["window_s"] * obs["chips"] * peaks["bf16_flops_per_s"])
    raise ValueError(f"unknown trace quantity {quantity!r}")
