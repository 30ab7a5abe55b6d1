"""A kernel's share of the memory roofline, in %: the bytes its calls
must move a step, over the chip's peak, over the time its calls took a
step in the traced slice.

The bytes sit with the benchmark: a counter of the program that counts
work from shapes (`bytes`: bytes over all dispatches of the window) over
the window's dispatches (`per`), the same work whatever implements it.
The time is the device time of the operations whose name holds `kernel`
(a Pallas call's `name`), summed over the slice and divided by the
slice's runs of the step program.

The harness removes the profiler's files once `xplane.reduce_run` has
reduced them, before any reader runs, so this reader takes the
operations from what the reduction kept: `breakdown.device_ops`, the ten
longest by name. Calls of one kernel over one shape take the same time,
so they stand together in that list or are cut together: where the
list's last entry is itself one of the kernel's, others may lie under
the cut and nothing is reported. None, too, where the trace names no
such operation (the plain path, the CPU) or the program has no such
counter.
"""

from benchmarks import xplane
from benchmarks.readers import counter_ratio


def read(obs, kernel: str, bytes: dict, per: dict):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    if not trace or not peaks or not trace.get("steps"):
        return None
    per_step = counter_ratio.read(obs, bytes, per)
    if not per_step:
        return None
    ops = trace["breakdown"]["device_ops"]
    mine = [seconds for name, seconds in ops if kernel in name]
    if not mine or (len(ops) >= xplane.TOP and kernel in ops[-1][0]):
        return None
    least = per_step / peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(mine) / trace["steps"])
