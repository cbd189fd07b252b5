"""Time `gpp_matmul` / `gpp_matmul_grouped` at pinned (block_k, block_n)
tiles on the served weight shapes, on one TPU chip.

For each shape the candidates are every pair of 128-lane divisors of K and
N whose bf16 weight tile holds 0.5-6 MiB, plus the planner's pick and the
tile pair planned before divisor tiles (`block_k = min(K, 2048)`,
`block_n = 256`), whose call zero-pads the weight first; the pad is timed
with it, as a step program pays it.  One timing is the median of `--reps`
runs of a jitted loop of `--iters` calls (a loop index in the activations
keeps XLA from hoisting the call), divided by `--iters`.

    python scripts/gpp_tile_sweep.py [--iters 16] [--reps 5] \
        [--out tile_sweep.jsonl]

Prints one JSON line per (shape, tiles) and, per shape, the candidates by
time.  Needs a TPU: times from another backend say nothing about the chip.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

from repro.core.schedule import round_up                     # noqa: E402
from repro.kernels.gpp_matmul import (                       # noqa: E402
    gpp_matmul, gpp_matmul_grouped)

MiB = 2 ** 20
# (name, experts or 0 for the flat kernel, M rows, K, N): the benchmark's
# decode programs (qwen2-7b 16 lanes, DeepSeek-V2-Lite 32, qwen1.5-0.5b 4)
SHAPES = [
    ("qwen2-7b gate/up", 0, 16, 3584, 18944),
    ("qwen2-7b down", 0, 16, 18944, 3584),
    ("qwen2-7b q/o", 0, 16, 3584, 3584),
    ("qwen2-7b k/v", 0, 16, 3584, 512),
    ("deepseek routed gate/up", 64, 32, 2048, 1408),
    ("deepseek routed down", 64, 32, 1408, 2048),
    ("deepseek shared down", 0, 32, 2816, 2048),
    ("qwen1.5-0.5b down", 0, 4, 2816, 1024),
]


def lane_divisors(n: int) -> "list[int]":
    return [t for t in range(128, n + 1, 128) if n % t == 0]


def candidates(K: int, N: int) -> "list[tuple[int, int]]":
    ks = lane_divisors(K) if K % 128 == 0 else [round_up(K, 8)]
    ns = lane_divisors(N)
    pairs = [(bk, bn) for bk in ks for bn in ns
             if MiB // 2 <= bk * bn * 2 <= 6 * MiB]
    return [(min(K, 2048), 256), (None, None)] + pairs


def timed(fn, x, w, iters: int, reps: int) -> float:
    def loop(x, w):
        def body(i, acc):
            xi = (x.astype(jnp.float32) + 1e-3 * i).astype(x.dtype)
            return acc + fn(xi, w).astype(jnp.float32)
        out = jax.eval_shape(fn, x, w)
        return jax.lax.fori_loop(0, iters, body,
                                 jnp.zeros(out.shape, jnp.float32))
    run = jax.jit(loop)
    run(x, w).block_until_ready()              # compile and warm up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run(x, w).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / iters


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="tile_sweep.jsonl")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    key = jax.random.PRNGKey(0)
    with out.open("w") as f:
        for name, E, M, K, N in SHAPES:
            lead = (E,) if E else ()
            kernel = gpp_matmul_grouped if E else gpp_matmul
            x = jax.random.normal(key, (*lead, M, K), jnp.bfloat16)
            w = jax.random.normal(key, (*lead, K, N), jnp.bfloat16)
            weight_bytes = max(E, 1) * K * N * 2
            rows = []
            for bk, bn in candidates(K, N):
                def fn(x, w, bk=bk, bn=bn):
                    return kernel(x, w, block_k=bk, block_n=bn)
                try:
                    s = timed(fn, x, w, args.iters, args.reps)
                except Exception as e:  # noqa: BLE001 — a refused tile
                    print(f"{name} {bk}x{bn}: {type(e).__name__}: "
                          f"{str(e)[:200]}", flush=True)
                    continue
                row = {"shape": name, "experts": E, "M": M, "K": K, "N": N,
                       "block_k": bk, "block_n": bn,
                       "tile_mib": bk * bn * 2 / MiB if bk else None,
                       "us": s * 1e6, "gb_s": weight_bytes / s / 1e9,
                       "device": dev.device_kind}
                rows.append(row)
                f.write(json.dumps(row) + "\n")
                f.flush()
            print(f"== {name} (E={E} M={M} K={K} N={N})")
            for r in sorted(rows, key=lambda r: r["us"]):
                tiles = ("planned" if r["block_k"] is None
                         else f"{r['block_k']}x{r['block_n']}")
                print(f"  {tiles:>12} {r['us']:9.1f} us "
                      f"{r['gb_s']:6.1f} GB/s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
