"""Finding a cell's fixed rate or depth: one run of a cell with keys of
its traffic file overridden. Never used for a measurement.

    python3 benchmarks/sweep.py --workload stream-512k.saturate --seed 5 \
        --seconds 10 --set inflight_frames=2

Prints one line: what was set, and the numbers that decide (README.md,
"How each fixed number in a traffic file was found").
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", nargs="+", default=[], metavar="KEY=JSON")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    override = {k: json.loads(v) for k, v in
                (item.split("=", 1) for item in args.set)}
    result, info = run.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), "tpu", t_process=_T_PROCESS,
                                traffic_override=override)
    print(json.dumps({"set": override, **info["end_to_end"],
                      "frames": info["frames"],
                      "rejected_events": info["rejected_events"],
                      "drain_s": info["drain_s"], "failed": result["failed"],
                      "device": result["device"],
                      "metrics": result["metrics"] if args.trace else None,
                      "correct": result["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
