"""One traced run of a benchmark cell, read for the loop's account.

A dev script, never used for a measurement the driver compares: it runs
`benchmarks/run.py`'s `run_cell` with `--trace 1` as the harness does and
keeps what the harness throws away: every `busy.*` and `loop.*` counter of
the window (the harness's line holds only the metrics `BENCHMARK.json`
names), and the profiler trace's `/host:CPU` plane, from which it lays
the device's ten longest idle gaps to what the loop's thread was doing in
them: which operator's step (`loop.<operator>`), which stage span inside
it, the selector's wait (`loop.select`), or callbacks (the line's gaps).

    chiprun -- python3 scripts/loop_account.py --workload stream-512k.steady --seed 7

prints the harness's two lines and then `{"loop_account": ...}`, also
written to `chiprun_out/loop_account/<workload>.<seed>.json`. `--ring-ms
20` also keeps every step of 20 ms or more in the tracer's slow-step ring
(the runtime keeps those of `observe_stall_ms`), to name a step that the
histogram counted and the ring was too coarse for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T_PROCESS = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import hostspans, run, xplane  # noqa: E402

LOOP = "loop."
SELECT = "loop.select"
CALLBACKS = "callbacks"


def counters_of(obs: dict) -> dict:
    """The window's seconds by counter, over the window's length."""
    window = obs["window_metrics"]
    seconds = obs["seconds"]
    shares = {name: value / seconds
              for name, value in sorted(window["counters"].items())
              if name.startswith(("busy.", "loop."))}
    out = {"seconds": seconds, "shares": shares,
           "long_step_s": window["histograms"].get("loop.long_step_s")}
    busy = shares.get("busy.loop")
    if busy is not None:
        operators = {n[len("busy.loop."):]: v for n, v in shares.items()
                     if n.startswith("busy.loop.")
                     and n != "busy.loop.unspanned"}
        out["identity"] = {
            "busy_plus_select": busy + shares.get("loop.select_s", 0.0),
            "operators_plus_callbacks": sum(operators.values()),
            "busy": busy}
    return out


def overlap(events: list, lo: float, hi: float) -> dict[str, float]:
    """ns of [lo, hi) under each name of `events` ([name, start, dur])."""
    out: dict[str, float] = {}
    for name, start, duration in events:
        a, b = max(start, lo), min(start + duration, hi)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def read_trace(trace_dir: str) -> dict | None:
    path = xplane.find(trace_dir)
    if path is None:
        return None
    planes = xplane.load(path)
    host = next((p for p in planes if p["name"] == hostspans.HOST_PLANE), None)
    if host is None:
        return None
    # the loop's thread: the line that holds the most step annotations
    line = max(host["lines"], key=lambda ln: sum(
        1 for e in ln["events"] if e[0].startswith(LOOP)), default=None)
    if line is None:
        return None
    stages = hostspans.span_names()
    steps = [e for e in line["events"]
             if e[0].startswith(LOOP) and e[0] != SELECT]
    waits = [e for e in line["events"] if e[0] == SELECT]
    spans = [e for e in line["events"] if hostspans.is_span(e[0], stages)]
    if not steps:
        return {"loop_thread": line["name"], "steps": 0}
    lo = min(e[1] for e in steps + waits)
    hi = max(e[1] + e[2] for e in steps + waits)
    step_ns = overlap(steps, lo, hi)
    wait_ns = sum(overlap(waits, lo, hi).values())
    tiled = xplane.merge([(s, s + d) for _, s, d in steps])
    nested = sum(1 for _, s, d in spans
                 if any(a <= s and s + d <= b for a, b in tiled))
    # which operator's steps each stage span ran in, in seconds
    starts = sorted((s, s + d, name[len(LOOP):]) for name, s, d in steps)
    inside: dict[str, dict[str, float]] = {}
    at = 0
    for name, s, d in sorted(spans, key=lambda e: e[1]):
        while at < len(starts) and starts[at][1] <= s:
            at += 1
        holder = starts[at][2] if at < len(starts) \
            and starts[at][0] <= s and s + d <= starts[at][1] else "no step"
        row = inside.setdefault(name, {})
        row[holder] = row.get(holder, 0.0) + d * 1e-9
    longest = []
    for name, s, d in sorted(steps, key=lambda e: -e[2])[:xplane.TOP]:
        longest.append({
            "operator": name[len(LOOP):], "ms": d * 1e-6,
            "stages_ms": {k: round(v * 1e-6, 3)
                          for k, v in overlap(spans, s, s + d).items()}})
    out = {
        "longest_steps": longest,
        "stage_in_operator": inside,
        "loop_thread": line["name"], "steps": len(steps),
        "line_s": (hi - lo) * 1e-9,
        "operators_s": {k[len(LOOP):]: v * 1e-9 for k, v in sorted(
            step_ns.items(), key=lambda kv: -kv[1])},
        "select_s": wait_ns * 1e-9,
        "callbacks_s": (hi - lo - sum(step_ns.values()) - wait_ns) * 1e-9,
        "stage_spans": len(spans), "stage_spans_inside_a_step": nested,
        "stage_spans_outside": sorted({e[0] for e in spans if not any(
            a <= e[1] and e[1] + e[2] <= b for a, b in tiled)}),
    }
    # the device's longest idle gaps, by what the loop's thread did in them
    ops = next((xplane._line(p, xplane.OPS_LINE) for p in planes
                if xplane.DEVICE_PLANE.match(p["name"])
                and xplane._line(p, xplane.OPS_LINE)), [])
    busy = xplane.merge([(s, s + d) for _, s, d in ops])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:])), reverse=True)
    rows = []
    for length, g_lo, g_hi in gaps[:xplane.TOP]:
        by_step = overlap(steps, g_lo, g_hi)
        waited = sum(overlap(waits, g_lo, g_hi).values())
        by_stage = overlap(spans, g_lo, g_hi)
        shares = {k[len(LOOP):]: v / length for k, v in by_step.items()}
        shares["select"] = waited / length
        shares[CALLBACKS] = max(
            1.0 - sum(by_step.values()) / length - waited / length, 0.0)
        rows.append({
            "seconds": length * 1e-9,
            "most": max(shares, key=shares.get),
            "shares": {k: round(v, 4) for k, v in sorted(
                shares.items(), key=lambda kv: -kv[1]) if v >= 0.005},
            "stages": {k: round(v / length, 4) for k, v in sorted(
                by_stage.items(), key=lambda kv: -kv[1])
                if v / length >= 0.005}})
    out["idle_gaps"] = rows
    out["device_busy_s"] = sum(b - a for a, b in busy) * 1e-9
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--ring-ms", type=float, default=None,
                    help="keep steps from this length in the slow-step ring "
                         "(the runtime's own threshold is observe_stall_ms)")
    ap.add_argument("--root", default=ROOT,
                    help="the tree whose BENCHMARK.json and data files to "
                         "run (a tiny copy, for a rehearsal on the CPU)")
    args = ap.parse_args(argv)

    account: dict = {"workload": args.workload, "seed": args.seed}
    reduce_run, per_layer = xplane.reduce_run, run.per_layer

    kept: dict = {}
    start_runtime = run.start_runtime

    async def keep_runtime(instance_id):
        rt = kept["rt"] = await start_runtime(instance_id)
        if args.ring_ms is not None:
            rt.tracer.stall_s = args.ring_ms / 1e3
        return rt

    def keep_trace(obs):
        account["trace"] = read_trace(obs["trace_dir"])
        return reduce_run(obs)

    def keep_counters(cell, obs):
        account["window"] = counters_of(obs)
        # the ring, on the window's clock: where each slow step fell
        t0, t1 = obs["trace_slice"]["t0"], obs["trace_slice"]["t1"]
        account["slow_steps"] = [
            {**step, "at_s": step["t_start"] - obs["start"],
             "in_window": obs["start"] <= step["t_start"] < obs["end"],
             "in_slice": t0 <= step["t_start"] < t1}
            for step in kept["rt"].tracer.slow_steps()]
        account["slice_at_s"] = [t0 - obs["start"], t1 - obs["start"]]
        return per_layer(cell, obs)

    xplane.reduce_run, run.per_layer = keep_trace, keep_counters
    run.start_runtime = keep_runtime
    result, info = run.run_cell(args.workload, args.seed, args.seconds, True,
                                args.platform, root=args.root,
                                t_process=_T_PROCESS)
    print(json.dumps({"info": info}), flush=True)
    print(json.dumps(result), flush=True)
    account["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
    account["correct"] = result["correct"]
    out_dir = os.path.join(ROOT, "chiprun_out", "loop_account")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.workload}.{args.seed}.json"),
              "w") as fh:
        json.dump(account, fh, indent=1)
    print(json.dumps({"loop_account": account}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
