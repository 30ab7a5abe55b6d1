"""From a jax.profiler trace to device busy time, step time and a breakdown.

`load` reads the `.xplane.pb` with `jax.profiler.ProfileData` into plain
lists; `reduce` works on those lists alone, so a small recorded trace
(tests/benchmarks/data) checks the arithmetic without a chip.

What the trace of a TPU holds, as read on a v5e: one plane a chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event for each operation
the chip ran and whose line `XLA Modules` has one event for each run of
a jitted program, named `jit_<function>(<fingerprint>)`. The program's
jitted steps carry no `named_scope` yet, so the reduction keys on those
names as the trace gives them.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10
NAME_CHARS = 160


def find(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> list[dict]:
    """Planes as plain data: [{name, lines: [{name, events: [[name,
    start_ns, duration_ns], ...]}]}]."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def module_base(name: str) -> str:
    """`jit_step(1234567)` -> `jit_step`."""
    return name.split("(", 1)[0]


def reduce(planes: list[dict], step_module: str = "jit_step") -> dict | None:
    """Busy seconds, steps and the breakdown of one traced slice, averaged
    over the chips that ran anything. None when no device plane has an
    operation on it (a CPU run, or a chip that was never driven)."""
    chips = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = _line(plane, OPS_LINE)
        if not ops:
            continue
        busy = merge([(s, s + d) for _, s, d in ops])
        per_op: dict[str, float] = {}
        for name, _, d in ops:
            per_op[name] = per_op.get(name, 0.0) + d
        modules = _line(plane, MODULES_LINE)
        steps = [(s, s + d) for name, s, d in modules
                 if module_base(name) == step_module]
        per_module: dict[str, float] = {}
        for name, _, d in modules:
            base = module_base(name)
            per_module[base] = per_module.get(base, 0.0) + d
        chips.append({
            "busy_ns": sum(hi - lo for lo, hi in busy),
            "gaps_ns": sorted((b[0] - a[1] for a, b in zip(busy, busy[1:])),
                              reverse=True)[:TOP],
            "per_op": per_op, "per_module": per_module,
            "steps": len(steps),
            "step_ns": sum(hi - lo for lo, hi in merge(steps)),
            "span_ns": busy[-1][1] - busy[0][0]})
    if not chips:
        return None
    n = len(chips)

    def mean_by_name(key: str) -> dict[str, float]:
        out: dict[str, float] = {}
        for c in chips:
            for name, d in c[key].items():
                out[name] = out.get(name, 0.0) + d / n
        return out

    per_op, per_module = mean_by_name("per_op"), mean_by_name("per_module")
    top = sorted(per_op.items(), key=lambda kv: kv[1], reverse=True)[:TOP]
    return {
        "chips": n,
        "busy_s": sum(c["busy_ns"] for c in chips) / n * 1e-9,
        "span_s": max(c["span_ns"] for c in chips) * 1e-9,
        # a step runs on every chip of a mesh at once: count it once
        "steps": max(c["steps"] for c in chips),
        "step_s": sum(c["step_ns"] for c in chips) / n * 1e-9,
        "modules": {k: v * 1e-9 for k, v in per_module.items()},
        "breakdown": {
            # XLA names an operation by its whole HLO line: keep its head
            "device_ops": [[name[:NAME_CHARS], d * 1e-9] for name, d in top],
            # the host's spans are not on the trace's clock yet, so a
            # gap cannot be laid to what the host was doing in it
            "idle_gaps": [["unattributed", g * 1e-9]
                          for g in chips[0]["gaps_ns"]]}}


def reduce_run(obs: dict) -> dict | None:
    """The traced slice of one run: `reduce` plus what the harness knows
    (the slice's length on the host's clock, and how many events a
    dispatch scored on average over the window)."""
    path = find(obs["trace_dir"])
    if path is None:
        return None
    out = reduce(load(path), obs["config"].get("step_module", "jit_step"))
    if out is None:
        return None
    t0, t1 = obs["trace_slice"]["t0"], obs["trace_slice"]["t1"]
    # the profiler starts a little before t0 and stops a little after t1:
    # where the device's own clock shows a longer span, that is the window
    out["window_s"] = max(t1 - t0, out["span_s"])
    dispatches = obs["window_metrics"]["counters"].get("scoring.dispatches")
    if out["steps"] and dispatches:
        # events the traced steps scored, by the window's own average
        out["events"] = out["steps"] * obs["events_in_window"] / dispatches
    else:
        out["events"] = 0.0
    return out
