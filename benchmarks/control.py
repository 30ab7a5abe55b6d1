"""The control of `correct`, at a cell's own size: the plain reference one
precision below the configuration's, put in the program's place.

    python benchmarks/control.py --config stream-512k --ticks 140 --seeds 1 2 3

Prints, for each seed, the numbers `correct` compares (the score gaps and
the alert mismatches) between the control and the reference, beside the
configuration's limits. Every seed has to fail a limit; the smallest
reading over the seeds is the limit's upper end (PERF.md, section 2).
tests/benchmarks keeps the same comparison at a size a test can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import compare, gen, models  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--ticks", type=int, required=True,
                    help="readings a device, as many as a run compares")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{args.config}.json")) as fh:
        cfg = json.load(fh)
    mc, limits = cfg["model_config"], cfg["limits"]
    model = models.load(cfg["model"])
    lower = compare.LOWER[cfg["compute_dtype"]]
    for seed in args.seeds:
        worst = {"score_gap_max": 0.0, "score_gap_mean": 0.0,
                 "alert_mismatches": 0}
        for i, fleet in enumerate(gen.fleets(cfg, seed)):
            hist = np.empty((fleet.devices, cfg["history_ticks"]), np.float32)
            for k in range(cfg["history_ticks"]):
                hist[:, k] = fleet.values(k, spikes=False)
            frames = np.stack([fleet.values(cfg["history_ticks"] + k)
                               for k in range(args.ticks)])
            fed = np.ones(frames.shape, bool)
            params = model.tenant_params(seed, i, mc)
            ref = model.run(params, hist, frames, fed, mc,
                            cfg["compute_dtype"])
            ctl = model.run(params, hist, frames, fed, mc, lower)
            g_max, g_mean = compare.score_gaps(ctl, ref)
            worst["score_gap_max"] = max(worst["score_gap_max"], g_max)
            worst["score_gap_mean"] = max(worst["score_gap_mean"], g_mean)
            worst["alert_mismatches"] += compare.alert_mismatches(
                ctl >= cfg["threshold"], ref, cfg["threshold"],
                limits["score_gap_max"])
        fails = [k for k, v in worst.items() if v > limits[k]]
        print(json.dumps({"config": args.config, "seed": seed,
                          "control": lower, "ticks": args.ticks,
                          **worst, "fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
