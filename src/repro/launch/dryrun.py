import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell
and extract the roofline terms from the compiled artifact.

The two lines above MUST run before any other import (jax locks the device
count on first init) — which is why this module must never be imported by
tests or benchmarks; run it as ``PYTHONPATH=src python -m repro.launch.dryrun``.

Usage:
  python -m repro.launch.dryrun                       # all 34 cells, both meshes
  python -m repro.launch.dryrun --arch kimi-k2-1t-a32b --shape train_4k
  python -m repro.launch.dryrun --multi-pod-only      # just the 512-chip pass
  python -m repro.launch.dryrun --out results/dryrun.json

Per cell it records: compile ok, memory_analysis (bytes/device),
cost_analysis FLOPs & bytes, and the collective-bytes breakdown parsed from
the optimized HLO — the inputs to EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs.base import SHAPES, cells_for
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_step
from repro.models import registry

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_COLL_RE = re.compile(
    r"(?P<shape>(?:\(|)[a-z0-9\[\],\s/{}]*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.I)
_SHAPE_RE = re.compile(r"(?P<dt>bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|pred)"
                       r"\[(?P<dims>[\d,]*)\]")


def _tensor_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dims = [int(d) for d in m.group("dims").split(",") if d]
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[m.group("dt")]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-operand bytes of every collective op in the optimized HLO.

    Counts each op once (start/done fusion pairs deduped by line) keyed by
    collective kind.  Bytes are per-PARTITION (SPMD module is per-device).
    """
    out: dict = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.search(
            r"=\s*([a-z0-9\[\],\s{}]*?)\s*"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
            r"(-start)?\(", line)
        if not m:
            continue
        lhs = line.split("=", 1)[0]
        kind = m.group(2)
        nbytes = _tensor_bytes(lhs) or _tensor_bytes(m.group(1))
        if nbytes == 0:
            # fall back: first shape on the line
            nbytes = _tensor_bytes(line)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    return out


# ---------------------------------------------------------------------------
# single-cell dry run
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             stream_mode: str | None = None, verbose: bool = True) -> dict:
    cfg = registry.get_config(arch)
    if stream_mode:
        from repro.core.streamer import StreamSettings
        cfg = cfg.with_(stream=StreamSettings(mode=stream_mode,
                                              ring_depth=cfg.stream.ring_depth))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "stream_mode": cfg.stream.mode,
        "ok": False,
    }
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            bundle = make_step(cfg, mesh, shape)
            lowered = bundle.fn.lower(*bundle.input_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            colls = collective_bytes(hlo)
            # loop-aware accounting: collectives/dots inside the scan-over-
            # layers while body execute num_superblocks times but appear once
            # in the HLO text (launch/roofline.py).
            from repro.launch import roofline as rl
            layers = cfg.num_superblocks
            colls_la = rl.collective_bytes_loop_aware(hlo, layers)
            flops_la, dot_bytes_la, dot_cov = rl.dot_stats_loop_aware(hlo, layers)
            bytes_la = rl.bytes_loop_aware(hlo, layers)
            rec.update(
                ok=True,
                lower_s=round(t_lower, 2),
                compile_s=round(t_compile, 2),
                flops_per_device=float(ca.get("flops", 0.0)),
                bytes_per_device=float(ca.get("bytes accessed", 0.0)),
                flops_per_device_loop_aware=flops_la,
                dot_coverage=round(dot_cov, 4),
                bytes_per_device_loop_aware=bytes_la,
                dot_bytes_per_device_loop_aware=dot_bytes_la,
                argument_bytes=int(ma.argument_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                alias_bytes=int(ma.alias_size_in_bytes),
                collectives={k: dict(v) for k, v in colls.items()},
                collective_bytes_per_device=sum(v["bytes"] for v in colls.values()),
                collectives_loop_aware={k: dict(v) for k, v in colls_la.items()},
                collective_bytes_per_device_loop_aware=sum(
                    v["bytes"] for v in colls_la.values()),
                generated_code_bytes=int(ma.generated_code_size_in_bytes),
            )
            if verbose:
                hbm = (rec["argument_bytes"] + rec["temp_bytes"]
                       + rec["output_bytes"] - rec["alias_bytes"])
                print(f"  ok  lower={t_lower:5.1f}s compile={t_compile:6.1f}s "
                      f"flops/dev={rec['flops_per_device']:.3e} "
                      f"hbm/dev={hbm/2**30:6.2f}GiB "
                      f"coll/dev={rec['collective_bytes_per_device']/2**30:7.3f}GiB",
                      flush=True)
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"  FAIL {type(e).__name__}: {str(e)[:200]}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch (default: all)")
    ap.add_argument("--shape", default=None, help="single shape (default: assigned)")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--stream-mode", default=None,
                    choices=["resident", "insitu", "naive_pp", "gpp"])
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(registry.ARCH_NAMES)
    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    results = []
    for arch in archs:
        cfg = registry.get_config(arch)
        shapes = [args.shape] if args.shape else cells_for(cfg)
        for shape_name in shapes:
            for mp in meshes:
                print(f"[{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
                      f"{' x ' + args.stream_mode if args.stream_mode else ''}]",
                      flush=True)
                results.append(run_cell(arch, shape_name, mp,
                                        stream_mode=args.stream_mode))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    mode = "a" if os.environ.get("DRYRUN_APPEND") else "w"
    existing = []
    if mode == "a" and os.path.exists(args.out):
        existing = json.load(open(args.out))
    with open(args.out, "w") as f:
        json.dump(existing + results, f, indent=1)
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} cells compiled; results -> {args.out}")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
