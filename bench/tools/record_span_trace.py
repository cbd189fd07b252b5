"""Record the small profiler trace that `bench/tests/test_bench_spans.py`
reads: the serving engine at smoke width (qwen1.5-0.5b, both kernels on
their reference paths), serving two requests to the end under the
profiler.  The first prompt takes two prefill chunks (the first is not
fetched), the second one.  Every `scheduler.schedule()` sleeps 2 ms on the
host (device idle inside `serve.schedule`), and a 1 ms sleep between steps
sits in a `harness.poll` annotation (device idle outside any step).

  python bench/tools/record_span_trace.py <out_dir>
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(out: str) -> int:
    import jax
    from repro.models import registry
    from repro.models import transformer as tf
    from repro.serving import ServeConfig, ServingEngine

    cfg = registry.get_config("qwen1.5-0.5b", smoke=True)
    engine = ServingEngine(
        cfg, tf.init_params(cfg, jax.random.PRNGKey(0)),
        ServeConfig(slots=2, max_len=64, block_size=8, prefill_chunk=16,
                    dense_kernel="ref", paged_attn_kernel="ref"))
    engine.submit([1] * 17, 2)           # compile every step shape first
    engine.run()
    plan = engine.scheduler.schedule

    def schedule():
        time.sleep(0.002)
        return plan()

    engine.scheduler.schedule = schedule
    engine.submit(list(range(3, 23)), 3)
    engine.submit(list(range(40, 45)), 4)
    jax.block_until_ready(engine.caches)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    while engine.pending:
        engine.step()
        with jax.profiler.TraceAnnotation("harness.poll"):
            time.sleep(0.001)
    jax.block_until_ready(engine.caches)
    jax.profiler.stop_trace()
    print(sorted(Path(out).rglob("*.xplane.pb")))
    print([dict(r) for r in engine.metrics][-5:])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
