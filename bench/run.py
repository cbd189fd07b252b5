"""Run one cell of the benchmark once.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<config>.json`, whose `model_type` names its
architecture's module, `bench/arch/<model_type>.py`) under a traffic mix
(`bench/traffic/<mix>.json`), with its serving settings (slots, max_len,
num_blocks, an open loop's rate_per_s, and any further `ServeConfig`
fields under "serve") and the limit of its check in
`bench/cells/<cell>.json`.  The run makes the weights on the
device from the seed, builds the engine through `make_engine` with both
kernels on `auto` (which must resolve to the Pallas kernels), warms up the
cell's two step shapes, then serves the mix for `--seconds` and reads every
metric the cell reports through `bench/metrics/<metric>.py`.  With
`--trace 1` a few seconds in the middle of the window run under the
profiler and the per-layer metrics are printed; with `--trace 0`, the
end-to-end ones.

After the window the served tokens of a sample of finished requests are
compared with the float32 reference (`bench/references/<ref>.py`).  The
last line of stdout is one JSON object: correct, attempted, failed,
metrics, device (and breakdown when traced); the numbers compared, each
beside its limit, are the last lines of stderr and the last key there.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  `--cpu-rehearsal` runs the cell's
configuration at a smoke width on the CPU with the kernels in interpret
mode, for tests; it never reports a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_AT = 0.4          # the traced span starts this far into the window
TRACE_SECONDS = 3.0     # and lasts this long (at most a third of the window)
SAMPLE_TOKENS = 256     # served tokens the reference checks, at least
SAMPLE_MAX = 6          # requests it checks, at most
# The limit on logit_gap_max for a CPU rehearsal at smoke width, where
# logits are ~5x smaller than at the published widths: sound runs of
# qwen2-7b-14L.chat read at most 0.00131 over 12 seeds, the fp8 control
# 0.0086-0.0256 over 4 (my CPU runs).  A chip run takes the cell's limit.
REHEARSAL_GAP_LIMIT = 0.005


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def cell_metrics(bench: dict, workload: str, kind: str) -> "list[dict]":
    """The metrics of `kind` ("end_to_end" or "per_layer") the cell
    reports: those that list it, or list no cells and (for a per-layer
    metric) move an end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


@dataclasses.dataclass
class Context:
    """What a metric's reader (`bench/metrics/<name>.py: read(ctx)`) may
    read.  Host times are `time.perf_counter()` seconds."""
    due: "dict[int, float]"           # rid -> when the request was due
    tokens: "dict[int, list[float]]"  # rid -> host time of each token
    t0: float                         # the window's start and end
    t_end: float
    setup_s: float
    rows: list                        # the engine's ledger rows, window
    steps: list                       # each window step's calls (adapter)
    arch: object                      # the module bench/arch/<model_type>.py
    shape: object                     # its Shape
    slots: int
    peaks: object                     # bench.peaks.Peaks (None on CPU)
    memory_peak_bytes: int
    traced: "tuple | None" = None     # (host start, end, first, end step)
    trace: object = None              # bench.trace_reduce.Trace
    busy_s: float = 0.0
    window_s: float = 0.0

    def traced_steps(self) -> list:
        return [] if self.traced is None else \
            self.steps[self.traced[2]:self.traced[3]]


class Harness:
    """The engine with the harness's bookkeeping: requests as submitted,
    when each was due, and when each of its tokens came."""

    def __init__(self, engine):
        self.engine = engine
        self.due: "dict[int, float]" = {}
        self.tokens: "dict[int, list[float]]" = {}
        self.requests: "dict[int, tuple]" = {}
        self.late: "list[float]" = []
        self.attempted = self.failed = 0

    def submit(self, r, due: float) -> "int | None":
        now = time.perf_counter()
        self.attempted += 1
        try:
            rid = self.engine.submit(r.prompt.tolist(), r.max_new)
        except ValueError:
            self.failed += 1
            return None
        self.due[rid] = due
        self.tokens[rid] = []
        self.requests[rid] = (r.prompt, r.max_new)
        self.late.append(now - due)
        return rid

    def poll(self, now: float) -> None:
        from bench import adapter
        for rid, n in adapter.progress(self.engine, self.tokens).items():
            times = self.tokens[rid]
            if n > len(times):
                times.extend([now] * (n - len(times)))


def _profile_options():
    """Device and host activity with the harness's annotations, without
    Python's own function calls (which would slow the host loop)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def serve_window(h: Harness, requests, mix: dict, seconds: float,
                 first: int, clients: "dict[int, int]", steps: list,
                 trace_dir: "Path | None"):
    """Drive the engine for `seconds`.  Open loop: requests[first:] are
    submitted when due.  Closed loop: `clients` maps each client's request
    in flight to the client, and a client submits the next of
    requests[first:] (cycling) when its reply is complete.  Returns (t0, t_end,
    traced), traced = (host start, host end, first step, end step) of the
    profiled span or None."""
    import jax
    from bench import adapter

    ann = jax.profiler.TraceAnnotation
    engine = h.engine
    open_loop = mix["loop"] == "open"
    t0 = time.perf_counter()
    trace_at, trace_len = t0 + TRACE_AT * seconds, min(TRACE_SECONDS,
                                                       seconds / 3)
    traced = span = None
    nxt = first

    def stop(span):
        jax.block_until_ready(engine.caches)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        return (span[0], t, span[1], len(steps))

    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        if trace_dir is not None and traced is None:
            if span is None and now >= trace_at:
                jax.block_until_ready(engine.caches)
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=_profile_options())
                span = (time.perf_counter(), len(steps))
            elif span is not None and now >= span[0] + trace_len:
                traced, span = stop(span), None
        if open_loop:
            with ann("harness.submit"):
                while (nxt < len(requests)
                       and t0 + requests[nxt].due_s <= now):
                    h.submit(requests[nxt], t0 + requests[nxt].due_s)
                    nxt += 1
        if not engine.pending:
            # nothing to serve until the next arrival (or the window's end)
            wake = (t0 + requests[nxt].due_s
                    if open_loop and nxt < len(requests) else t0 + seconds)
            with ann("harness.wait"):
                time.sleep(max(0.0, min(wake, t0 + seconds) - now))
            continue
        with ann("engine.step"):
            engine.step()
        now = time.perf_counter()
        with ann("harness.poll"):
            h.poll(now)
            if not open_loop:
                for rid in [r for r in clients
                            if adapter.finished(engine, r) is not None]:
                    client = clients.pop(rid)
                    # the clients cycle through the mix's requests
                    new = h.submit(requests[nxt % len(requests)], now)
                    nxt += 1
                    if new is not None:
                        clients[new] = client
    if span is not None:
        traced = stop(span)
    jax.block_until_ready(engine.caches)
    return t0, time.perf_counter(), traced


def check_answers(outputs, requests, hf, params, ref, seed, control=False):
    """Compare the served tokens of a sample of finished requests — the
    longest, then others drawn from the seed — with the reference run over
    each prompt and its served tokens.  Returns (worst gap, served tokens
    checked, requests checked, answers): the widest gap by which a served
    token's reference logit lies below the reference's best at its
    position, and the answers that were compared.

    With `control`, the control's answers take the program's place: at the
    same positions of the same prompts and served tokens, the token that
    the reference in the precision below the configuration's puts first.
    The worst gap is then theirs, and also returned is the program's own
    under `program_gap` (a sound reading from the same window)."""
    import numpy as np
    if not outputs:
        return math.inf, 0, 0, {}, math.inf
    rng = np.random.default_rng(abs(int(seed)) + 1)
    size = {r: len(requests[r][0]) + len(outputs[r]) for r in outputs}
    longest = max(outputs, key=lambda r: (size[r], -r))
    pick, served = [longest], len(outputs[longest])
    for rid in rng.permutation(sorted(set(outputs) - {longest})):
        if served >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX:
            break
        pick.append(int(rid))
        served += len(outputs[int(rid)])
    worst = own = 0.0
    answers = {}
    for rid in pick:
        prompt = requests[rid][0]
        out = np.asarray(outputs[rid], np.int64)
        toks = np.concatenate([prompt, out[:-1]])
        pos = np.arange(len(prompt) - 1, len(toks))
        lg = ref.logits(hf, params, toks, pos)
        if not np.isfinite(lg).all():
            return math.inf, served, len(pick), answers, math.inf
        rows = np.arange(len(out))
        own = max(own, float((lg.max(-1) - lg[rows, out]).max()))
        if control:
            out = ref.logits(hf, params, toks, pos,
                             precision="fp8").argmax(-1)
        answers[rid] = [int(t) for t in out]
        worst = max(worst, float((lg.max(-1) - lg[rows, out]).max()))
    return worst, served, len(pick), answers, own


def traced_breakdown(ctx: Context) -> dict:
    """The device's top operations and its idle time by host annotation
    over the traced span."""
    from bench import trace_reduce as tr
    marks = [a for a in ctx.trace.annotations if a.name == "engine.step"]
    launches = []
    for step, mark in zip(ctx.traced_steps(), marks):
        programs = (step["prefill"] is not None) + bool(step["decode"])
        launches += [mark.start_ns] * programs
    progs = {"jit__prefill", "jit__decode"}
    ctx.trace.modules = {k: [e for e in v if e.name.split("(")[0] in progs]
                         for k, v in ctx.trace.modules.items()}
    offset = tr.device_offset(ctx.trace, launches)
    lo = marks[0].start_ns if marks else 0.0
    hi = lo + ctx.window_s * 1e9
    return {"device_ops": tr.top_ops(ctx.trace, 10),
            "idle_gaps": tr.idle_gaps(ctx.trace, lo, hi, offset)[:10]}


def run(argv=None, *, cell_overrides=None, check=True, control=False
        ) -> dict:
    """One run of a cell.  The keywords serve the tools under
    `bench/tools` (a rate sweep, the calibration of the check's limit):
    `cell_overrides` replaces keys of the cell's file, `check=False` skips
    the comparison with the reference, `control=True` puts the control's
    answers in the program's place (the run has to come out not correct)
    and keeps the program's own reading under harness.program_gap_max."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="smoke width, kernels in interpret mode, on the CPU")
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.perf_counter()
    rehearsal = args.cpu_rehearsal

    bench = load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in bench["workloads"]
               if w["name"] == args.workload), None)
    if wl is None:
        raise ValueError(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = dict(load_json(BENCH / "cells" / f"{args.workload}.json"),
                **(cell_overrides or {}))
    from bench import gen, model
    hf = model.load_config(wl["config"])
    arch = model.arch_for(hf)
    mix = gen.load_mix(wl["traffic"])

    # the compile cache lives inside the checkout, at a fixed path (the
    # path is part of the cache's key), and the program is given it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    dev = devices[0]
    if not rehearsal and (dev.platform != "tpu"
                          or len(devices) < wl["chips"]):
        raise NoChip(f"the cell needs {wl['chips']} TPU chip(s); JAX sees "
                     f"{len(devices)} {dev.platform} device(s)")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from bench import adapter, peaks
    from repro.kernels.ops import resolve_dense_mode
    from repro.models import transformer as tf
    from repro.serving import ServeConfig, make_engine

    if rehearsal:
        # smoke width, short requests, and arrivals frequent enough that a
        # few seconds' window finishes some, whatever the cell's rate
        hf = arch.smoke_hf(hf)
        cell = dict(cell, max_len=256, num_blocks=0,
                    rate_per_s=max(2.0, cell.get("rate_per_s", 0.0)))
        mix = dict(mix, prompt=dict(mix["prompt"], min=8, max=96, median=32),
                   output=dict(mix["output"], min=2, max=16, median=6))
    cfg = arch.model_config(wl["config"], hf)
    mode = "interpret" if rehearsal else "auto"
    want = ("interpret", "interpret") if rehearsal else ("kernel", "pallas")
    params = model.make_weights(tf.param_specs(cfg), args.seed)
    engine = make_engine(cfg, params, ServeConfig(
        slots=cell["slots"], max_len=cell["max_len"],
        num_blocks=cell["num_blocks"], dense_kernel=mode,
        paged_attn_kernel=mode, **cell.get("serve", {})))
    routes = {str(p[-1].key): resolve_dense_mode(mode, leaf[0])
              for p, leaf in jax.tree_util.tree_leaves_with_path(
                  params["blocks"]) if str(p[-1].key) in arch.KERNEL_WEIGHTS}
    if (set(routes) != arch.KERNEL_WEIGHTS or set(routes.values()) != {want[0]}
            or engine.paged_attn_mode != want[1]):
        raise RuntimeError(f"kernel routing resolved to {routes}, paged "
                           f"attention to {engine.paged_attn_mode}")

    # warm up the cell's step shapes: (1, chunk) prefill, (slots, 1)
    # decode, and the slice of the final chunk's logits
    engine.submit([1] * (engine.chunk + 1), 2)
    engine.run()
    jax.block_until_ready(engine.caches)
    compiled = dict(engine.trace_counts)

    h = Harness(engine)
    requests = gen.make_requests(mix, args.seed,
                                 gen.count_for(mix, cell, args.seconds),
                                 cfg.vocab_size,
                                 rate_per_s=cell.get("rate_per_s", 0.0))
    clients: "dict[int, int]" = {}
    first = 0
    if mix["loop"] == "closed":
        # set-up the traffic needs: each client's first request admitted
        # and prefilled, so that the window opens on long contexts
        for k in range(mix["clients"]):
            rid = h.submit(requests[k], time.perf_counter())
            if rid is not None:
                clients[rid] = k
        first = mix["clients"]
        while engine.scheduler.waiting or any(
                p != "decode" for p in engine.scheduler.phase.values()):
            engine.step()
        h.poll(time.perf_counter())
    jax.block_until_ready(engine.caches)
    setup_s = time.perf_counter() - t_start

    steps: list = []
    adapter.record_plans(engine, steps)
    rows0 = len(engine.metrics)
    trace_dir = (Path(tempfile.mkdtemp(prefix="bench_trace_"))
                 if args.trace else None)
    try:
        t0, t_end, traced = serve_window(h, requests, mix, args.seconds,
                                         first, clients, steps, trace_dir)
        rows = [engine.metrics[i] for i in range(rows0, len(engine.metrics))]
        if dict(engine.trace_counts) != compiled:
            raise RuntimeError(f"a step shape compiled inside the window: "
                               f"{compiled} -> {engine.trace_counts}")
        stats = [d.memory_stats() or {} for d in devices[:wl["chips"]]]
        mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        ctx = Context(
            due=h.due, tokens=h.tokens, t0=t0, t_end=t_end, setup_s=setup_s,
            rows=rows, steps=steps, arch=arch, shape=arch.Shape.from_hf(hf),
            slots=cell["slots"],
            peaks=None if rehearsal else peaks.peaks_for(dev.device_kind),
            memory_peak_bytes=mem_peak, traced=traced)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": mem_peak}
        breakdown = None
        if args.trace:
            from bench import trace_reduce
            ctx.trace = trace_reduce.load(trace_dir)
            ctx.window_s = traced[1] - traced[0]
            ctx.busy_s = trace_reduce.busy_ns(ctx.trace) / 1e9
            device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
            breakdown = traced_breakdown(ctx)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in cell_metrics(bench, args.workload,
                          "per_layer" if args.trace else "end_to_end"):
        value = model.load_module(BENCH / "metrics" / f"{m['name']}.py"
                                  ).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the check, once the program's state is freed
    outputs = {rid: out for rid in h.tokens
               if (out := adapter.finished(engine, rid)) is not None}
    outstanding = h.engine.pending
    engine.caches = None
    del engine, h.engine
    gc.collect()
    ref = model.load_module(BENCH / "references" / f"{hf['reference']}.py")
    worst, served, checked, answers, own = (
        check_answers(outputs, h.requests, hf, params, ref, args.seed,
                      control) if check else (math.nan, 0, 0, {}, math.nan))
    if control:
        outputs = {**outputs, **answers}
    malformed = sum(len(out) != h.requests[rid][1]
                    or not all(0 <= t < cfg.vocab_size for t in out)
                    for rid, out in outputs.items())
    checks = {
        "logit_gap_max": {"value": worst, "limit": (
            REHEARSAL_GAP_LIMIT if rehearsal else cell["logit_gap_limit"])},
        "malformed_answers": {"value": malformed, "limit": 0},
    }
    correct = bool(checked > 0 and all(c["value"] <= c["limit"]
                                       for c in checks.values()))
    result = {"correct": correct, "attempted": h.attempted,
              "failed": h.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["harness"] = {
        "requests_checked": checked, "served_tokens_checked": served,
        "generator_late_max_ms": 1e3 * max(h.late, default=0.0),
        "window_s": t_end - t0, "steps": len(rows),
        "finished": len(outputs), "outstanding_at_end": outstanding,
        "unstarted_at_end": sum(not ts for ts in h.tokens.values()),
        "queue_depth_last_third": (
            sum(r["queue_depth"] for r in rows[2 * len(rows) // 3:])
            / max(1, len(rows) - 2 * len(rows) // 3))}
    if control:
        result["harness"]["program_gap_max"] = own
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
