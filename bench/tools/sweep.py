"""Find a cell's knee once: serve its mix at several fixed rates, one run
each in this one process (the chip belongs to it), and print what each
rate sustained.  A rate is sustained when the backlog does not grow: at
the window's end at most `BACKLOG` requests still wait for their first
token, and the queue for a lane stays near empty through the last third.

  python bench/tools/sweep.py --workload qwen2-7b-14L.chat \
      --rates 0.4,0.55,0.7 --seconds 40 --seed 11
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

BACKLOG = 3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--apply", action="store_true")
    args = ap.parse_args()
    from bench import run
    knee = 0.0
    for rate in [float(r) for r in args.rates.split(",")]:
        res = run.run(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds)],
                      cell_overrides={"rate_per_s": rate}, check=False)
        row = {"rate_per_s": rate, **{k: v["value"] for k, v in
                                      res["metrics"].items()},
               **res["harness"], "attempted": res["attempted"]}
        print("SWEEP " + json.dumps(row), flush=True)
        if (row["unstarted_at_end"] <= BACKLOG
                and row["queue_depth_last_third"] < 1.0):
            knee = max(knee, rate)
    print(f"KNEE {knee}", flush=True)
    if args.apply and knee > 0:
        path = run.BENCH / "cells" / f"{args.workload}.json"
        cell = json.loads(path.read_text())
        cell["rate_per_s"] = round(0.8 * knee, 3)
        path.write_text(json.dumps(cell, indent=1) + "\n")
        print(f"RATE {cell['rate_per_s']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
