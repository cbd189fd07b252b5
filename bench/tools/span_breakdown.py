"""Run one cell traced, as `bench/run.py --trace 1` does, and put the
device's idle time inside the engine's steps down to the engine's own
spans (`bench/spans.py`).

  python bench/tools/span_breakdown.py --workload qwen2-7b-14L.chat \
      --seed 11 --seconds 51

Prints the run's result line, then one JSON line: the traced span's idle
time by `serve.*` span (`idle_in_step`, and again with the shift at the
top of its bracket), the device -> host shift and its slack,
`step_host_idle_ms`, the idle time the harness's own annotations
charge to `engine.step`, and the share of it that the stage spans (every
`serve.*` span but `serve.step`) cover.  It reads nothing the result line
does not, from the same trace.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--keep", default=None,
                    help="copy the run's .xplane.pb into this directory")
    args = ap.parse_args()
    from bench import run, spans
    from bench import trace_reduce as tr

    seen = {}
    load = tr.load

    def load_and_keep(path):
        # the run deletes its trace once reduced: read the spans first,
        # and hand the run a copy (it filters the copy's programs)
        seen["trace"], seen["spans"] = load(path), spans.load(path)
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(tr.find_xplane(path), Path(args.keep) / (
                f"{args.workload}.{args.seed}.xplane.pb"))
        return dataclasses.replace(seen["trace"])

    tr.load = load_and_keep
    try:
        result = run.run(["--workload", args.workload, "--seed",
                          str(args.seed), "--seconds", str(args.seconds),
                          "--trace", "1"]
                         + ["--cpu-rehearsal"] * args.cpu_rehearsal)
    finally:
        tr.load = load
    print(json.dumps(result), flush=True)
    trace, sp = seen["trace"], seen["spans"]
    marks = [a for a in trace.annotations if a.name == "engine.step"]
    lo = marks[0].start_ns
    hi = lo + result["device"]["window_s"] * 1e9
    found = spans.span_offset(trace, sp)
    out = {"workload": args.workload, "seed": args.seed,
           "serve_spans": sum(s.name.startswith(spans.PREFIX) for s in sp),
           "offset_ns": None, "offset_slack_us": None, "idle_in_step": None,
           "step_host_idle_ms": spans.host_idle_ms(trace, sp, lo, hi)}
    engine_step = dict(result["breakdown"]["idle_gaps"]).get("engine.step")
    out["engine_step_idle_s"] = engine_step
    if found is not None:
        idle = spans.idle_in_step(trace, sp, lo, hi, found[0])
        stages = sum(v for k, v in idle if k not in ("none", "serve.step"))
        out.update(offset_ns=found[0], offset_slack_us=found[1] / 1e3,
                   idle_in_step=idle,
                   # the same split at the other end of the shift's bracket
                   idle_in_step_at_slack=spans.idle_in_step(
                       trace, sp, lo, hi, found[0] + found[1]),
                   stage_share_of_engine_step=(
                       stages / engine_step if engine_step else None))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
