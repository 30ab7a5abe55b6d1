"""Busy seconds of one host stage for each second of the window: the
program's counter `busy.<stage>` (kernel/tracing.py: every `Tracer.span`
of the stage adds its seconds, the collector's pauses go to `busy.gc`)
over the window's length. The stage whose share is highest while those
before it wait is the host's bottleneck operator.

A share of a host's second is a utilization, and a utilization comes
from a chip run alone: a rehearsal on the CPU, whose trace has no device
plane, reports none, as it reports no `device_idle`, the number these
shares explain. A program without the counter (one that predates it)
gives nothing to read."""


def read(obs, counter: str):
    if not obs.get("trace") or not obs.get("seconds"):
        return None
    busy = obs["window_metrics"]["counters"].get(counter)
    if busy is None:
        return None
    return busy / obs["seconds"]
