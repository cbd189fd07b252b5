"""Readings that set a cell's limit on `logit_gap_max`: the program's on
many seeds, and the control's (the reference in the precision below the
configuration's, bench/references) on the first few, all in this one
process with a short window at the cell's own load.

  python bench/tools/calibrate.py --workload qwen2-7b-14L.chat \
      --seeds 101,102,103 --control 3 --seconds 20
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench import run
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        res = run.run(["--workload", args.workload, "--seed", str(seed),
                       "--seconds", str(args.seconds)],
                      control=i < args.control)
        h = res["harness"]
        print("CALIBRATE " + json.dumps({
            "seed": seed, "control": i < args.control,
            "correct": res["correct"],
            "logit_gap_max": h.get("program_gap_max",
                                   res["checks"]["logit_gap_max"]["value"]),
            "control_gap_max": (res["checks"]["logit_gap_max"]["value"]
                                if "program_gap_max" in h else None),
            "served_tokens_checked": h["served_tokens_checked"],
            "requests_checked": h["requests_checked"],
            "finished": h["finished"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
