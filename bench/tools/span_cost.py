"""What one engine span (`repro.obs.trace.phase`, telemetry off) costs the
host, with the profiler off and with it on: the mean over many empty
spans, against the same loop with no span.

  python bench/tools/span_cost.py
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

N = 200_000


def per_span_us(with_span: bool) -> float:
    from repro.obs.trace import NULL_TRACE, phase
    t0 = time.perf_counter()
    if with_span:
        for _ in range(N):
            with phase(NULL_TRACE, "serve.sample"):
                pass
    else:
        for _ in range(N):
            pass
    return (time.perf_counter() - t0) / N * 1e6


def main() -> int:
    import jax
    off = per_span_us(True) - per_span_us(False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        on = per_span_us(True) - per_span_us(False)
        jax.profiler.stop_trace()
    print(json.dumps({"platform": jax.devices()[0].platform, "spans": N,
                      "us_per_span_profiler_off": off,
                      "us_per_span_profiler_on": on}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
