"""From a jax.profiler trace to what the host was doing in the device's
longest idle gaps.

The program writes its synchronous stages into the trace as
`jax.profiler.TraceAnnotation`s (sitewhere_tpu/kernel/tracing.py,
`Tracer.span`): with `host_tracer_level >= 1` they land in the plane
`/host:CPU`, one line a thread, on the clock the device planes' `XLA Ops`
are on. `load` keeps those lines beside the device planes as plain lists
(as `xplane.load` does, so a small recorded trace under
tests/benchmarks/data checks `attribute` without a chip); `attribute`
names each of the longest gaps by the program span that covers most of
it on any one thread.

A span is known by its name: a stage of the program's own inventory
(`TRACE_STAGES`) or a collection, `gc.gen<N>`. The harness does not call
this yet: its reduction (`xplane.reduce`) drops the host plane, and a
`benchmark` PR wires `attribute` into its `idle_gaps`. Until then:

    python benchmarks/hostspans.py <trace dir or .xplane.pb>
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import xplane  # noqa: E402

HOST_PLANE = "/host:CPU"
GC_SPAN = re.compile(r"^gc\.gen\d$")
HOST_IDLE = "host_idle"
COVERS = 0.1            # a span names a gap from a tenth of it upwards


def span_names() -> frozenset:
    from sitewhere_tpu.analysis.registry import TRACE_STAGES

    return frozenset(name for name, _kind in TRACE_STAGES)


def is_span(name: str, stages: frozenset) -> bool:
    return name in stages or bool(GC_SPAN.match(name))


def load(path: str) -> list[dict]:
    """`xplane.load`'s planes, cut to what `attribute` reads: each device
    plane's `XLA Ops` line, and of the host plane each thread line that
    holds a program span, with those spans alone."""
    stages = span_names()
    planes = []
    for plane in xplane.load(path):
        if xplane.DEVICE_PLANE.match(plane["name"]):
            lines = [line for line in plane["lines"]
                     if line["name"] == xplane.OPS_LINE]
        elif plane["name"] == HOST_PLANE:
            lines = [{"name": line["name"],
                      "events": [e for e in line["events"]
                                 if is_span(e[0], stages)]}
                     for line in plane["lines"]]
            lines = [line for line in lines if line["events"]]
        else:
            continue
        planes.append({"name": plane["name"], "lines": lines})
    return planes


def attribute(planes: list[dict], top: int = xplane.TOP) -> list[list]:
    """`[name, seconds]` for each of the `top` longest gaps between `XLA
    Ops` on the first device that ran any, longest first: `name` is the
    program span whose events on one host thread cover the largest part
    of the gap, or `host_idle` where none covers a tenth of it."""
    stages = span_names()
    ops = next((xplane._line(p, xplane.OPS_LINE) for p in planes
                if xplane.DEVICE_PLANE.match(p["name"])
                and xplane._line(p, xplane.OPS_LINE)), [])
    busy = xplane.merge([(s, s + d) for _, s, d in ops])
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:top]
    threads = [line["events"] for p in planes if p["name"] == HOST_PLANE
               for line in p["lines"]]
    out = []
    for length, lo, hi in gaps:
        best, best_ns = HOST_IDLE, COVERS * length
        for events in threads:
            by_name: dict[str, list] = {}
            for name, start, duration in events:
                if start < hi and start + duration > lo \
                        and is_span(name, stages):
                    by_name.setdefault(name, []).append(
                        (max(start, lo), min(start + duration, hi)))
            for name, parts in by_name.items():
                covered = sum(b - a for a, b in xplane.merge(parts))
                if covered >= best_ns:
                    best, best_ns = name, covered
        out.append([best, length * 1e-9])
    return out


def main(argv: list[str]) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find(path)
    print(json.dumps(attribute(load(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
