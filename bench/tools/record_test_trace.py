"""Record the small profiler trace that `bench/tests/test_bench_trace_reduce.py`
reads: two host-annotated "steps", each one `gpp_matmul` call and one
`paged_attention` call at small shapes, with a host sleep between them
inside a `harness.poll` annotation (an idle gap with a known cause).

  python bench/tools/record_test_trace.py chiprun_out/test_trace
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import dense, paged_attn

    mode_d, mode_p = (("kernel", "pallas") if jax.default_backend() == "tpu"
                      else ("interpret", "interpret"))
    k = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k[0], (8, 1024), jnp.bfloat16)
    w = jax.random.normal(k[1], (1024, 2048), jnp.bfloat16)
    pool_k = jax.random.normal(k[2], (17, 16, 2, 128), jnp.bfloat16)
    pool_v = jax.random.normal(k[3], (17, 16, 2, 128), jnp.bfloat16)
    tables = jnp.arange(1, 17, dtype=jnp.int32).reshape(4, 4)
    positions = jnp.array([63, 40, 17, 5], jnp.int32)

    @jax.jit
    def step(x, w, pk, pv):
        y = dense(x, w, mode=mode_d)
        q = y[:4, :1024].reshape(4, 1, 8, 128)
        o = paged_attn(q, pk, pv, tables, positions, num_kv_heads=2,
                       scale=0.088, mode=mode_p)
        return y, o

    jax.block_until_ready(step(x, w, pool_k, pool_v))
    jax.profiler.start_trace(out)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("engine.step"):
            jax.block_until_ready(step(x, w, pool_k, pool_v))
        with jax.profiler.TraceAnnotation("harness.poll"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    print(sorted(Path(out).rglob("*.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
