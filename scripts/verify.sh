#!/usr/bin/env bash
# Tier-1 verify gate (ROADMAP.md): the full test suite, -x -q, followed by a
# serving smoke run (paged engine end-to-end through launch/serve).
#
# Usage: scripts/verify.sh [extra pytest args]
#   e.g. scripts/verify.sh -m tier1      # only the tier1-marked fast gate
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
python -m pytest -x -q "$@"
echo "--- serving smoke (paged engine) ---"
python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
    --requests 3 --max-new 4 --slots 2 --max-len 64
echo "--- paged-attention kernel parity smoke (interpret mode) ---"
python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.models import registry, transformer as tf
from repro.serving import ServeConfig, ServingEngine

cfg = registry.get_config("qwen1.5-0.5b", smoke=True)
params = tf.init_params(cfg, jax.random.PRNGKey(0))
prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]

def run(mode):
    eng = ServingEngine(cfg, params, ServeConfig(
        slots=2, max_len=64, block_size=8, prefill_chunk=8,
        paged_attn_kernel=mode))
    rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
    res = eng.run()
    return [res[r] for r in rids]

gather, kernel = run("ref"), run("interpret")
assert gather == kernel, (gather, kernel)
print(f"paged-attention parity OK (gather == kernel): {kernel}")
PY
echo "--- prefix-cache smoke (shared system prompt, parity vs off) ---"
python - <<'PY'
import jax, numpy as np
from repro.models import registry, transformer as tf
from repro.serving import ServeConfig, ServingEngine

cfg = registry.get_config("qwen1.5-0.5b", smoke=True)
params = tf.init_params(cfg, jax.random.PRNGKey(0))
system = list(range(100, 124))            # 24-token shared system prompt
prompts = [system + [7, 8], system + [9], system + [11, 12, 13]]

def run(prefix):
    eng = ServingEngine(cfg, params, ServeConfig(
        slots=2, max_len=64, block_size=8, prefill_chunk=8,
        prefix_cache=prefix))
    outs = []
    for p in prompts:                     # sequential: later turns can hit
        rid = eng.submit(p, max_new_tokens=4)
        eng.run()
        outs.append(eng.result(rid))
    return outs, eng

cold, _ = run(False)
warm, eng = run(True)
assert warm == cold, (warm, cold)
hit_rate = eng.prefix.hit_rate()
hit_tokens = eng.prefix.hit_tokens
assert hit_rate > 0 and hit_tokens > 0, (hit_rate, hit_tokens)
eng.kv.check_invariants(eng.prefix.held_blocks())
print(f"prefix-cache parity OK (shared == cold), hit_rate={hit_rate:.2f} "
      f"hit_tokens={hit_tokens}")
PY
echo "--- speculation smoke (batched verify, greedy parity vs off) ---"
python - <<'PY'
import jax, numpy as np
from repro.models import registry, transformer as tf
from repro.serving import ServeConfig, ServingEngine

cfg = registry.get_config("qwen1.5-0.5b", smoke=True)
params = tf.init_params(cfg, jax.random.PRNGKey(0))
# repetitive multi-turn trace: prompt-lookup self-drafting feeds on it
prompts = [np.tile([5, 6, 7, 8], 6).tolist(), [1, 2, 3],
           np.tile([9, 3], 10).tolist()]

def run(spec):
    eng = ServingEngine(cfg, params, ServeConfig(
        slots=2, max_len=128, speculation=spec,
        draft_len=4 if spec else 0))
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    res = eng.run()
    return [res[r] for r in rids], eng

off, _ = run(False)
on, eng = run(True)
assert on == off, (on, off)
acc = eng.acceptance_rate()
assert acc > 0, acc
eng.kv.check_invariants()
print(f"speculation parity OK (spec == off), acceptance_rate={acc:.2f} "
      f"steps={len(eng.metrics)} traces={eng.trace_counts}")
PY
echo "--- observability smoke (trace + metrics through launch/serve) ---"
OBS_DIR="$(mktemp -d)"
trap 'rm -rf "$OBS_DIR"' EXIT
python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
    --requests 3 --max-new 4 --slots 2 --max-len 64 \
    --trace-out "$OBS_DIR/trace.json" --metrics-out "$OBS_DIR/metrics.jsonl"
OBS_DIR="$OBS_DIR" python - <<'PY'
import json, math, os

d = os.environ["OBS_DIR"]
doc = json.load(open(os.path.join(d, "trace.json")))
evs = doc["traceEvents"]
assert evs, "empty trace"
pids = {e["pid"] for e in evs}
assert pids == {1, 2}, f"trace tracks: {pids}"   # serving/requests
assert any(e.get("ph") == "X" and e["name"] == "step" for e in evs)
assert any(e.get("cat") == "phase" and e["name"] == "serve.decode.fetch"
           for e in evs), "no engine phases"
(line,) = open(os.path.join(d, "metrics.jsonl")).read().splitlines()[-1:]
snap = json.loads(line)
req = snap["requests"]
for hist in ("ttft", "tpot"):
    s = req[hist]
    assert s["count"] > 0, f"no {hist} observations"
    for q in ("p50", "p99"):
        assert math.isfinite(s[q]) and s[q] > 0, (hist, q, s)
assert snap["ledger"]["total_hbm_bytes"] > 0
print(f"observability smoke OK: {len(evs)} trace events "
      f"({doc['otherData']['dropped_events']} dropped), "
      f"ttft_p50={req['ttft']['p50']:.3f}s tpot_p50={req['tpot']['p50']:.4f}s")
PY
