"""Smoke run of the system on a TPU: does the main path start and answer?

  python chip_smoke.py                  one chip: qwen1.5-0.5b at its published
                                        widths (24 layers, d_model 1024, vocab
                                        151936, bf16, random weights from
                                        --seed) behind `make_engine`, both
                                        Pallas kernels on; 8 requests of
                                        128-512 prompt tokens, 32 new tokens
                                        each; then the kernel path's logits
                                        against the ref path's
  python chip_smoke.py --chips 4        four chips, and only this: a few train
                                        steps on a (2,2) data x model mesh
                                        against the same steps on a (1,1) mesh
  python chip_smoke.py --cpu-rehearsal  the smoke-width config with the
                                        kernels in interpret mode on the CPU
                                        (with --chips 4: on four virtual CPU
                                        devices)

The last line of stdout is one JSON object, {"ok": true, "device": {...}},
naming the device as JAX reports it.  The script exits non-zero, before
printing it, when no TPU is found (unless --cpu-rehearsal), when a kernel
mode resolves to "ref", when a result exceeds its bound, or when any phase
fails.  Times it prints are a smoke timing, not a benchmark.  Everything
runs in this one process: a chip belongs to the process that touched it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Largest |logits_kernel - logits_ref| over one prefill chunk and a few
# decode steps, relative to max |logits_ref|.  Both paths compute in bf16
# with f32 accumulation but round and sum in a different order (ring tiles,
# online softmax), and the gaps compound over 24 layers.
LOGITS_REL_BOUND = 5e-2
# Largest |loss_(2,2) - loss_(1,1)| relative to loss over the train steps:
# the sharded step reduces in a different order.
LOSS_REL_BOUND = 1e-2

SLOTS, MAX_LEN, MAX_NEW = 8, 2048, 32
PROMPT_LENS = (128, 512)
CHECK_DECODE_STEPS = 3
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 128


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _compile_seconds():
    """Sum of backend compile durations JAX reports from now on (a cache
    hit reports its retrieval time instead)."""
    import jax
    total = [0.0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def _hbm(device) -> str:
    """Device memory in use now and its peak so far, from memory_stats()."""
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return (f"in use {stats['bytes_in_use'] / 2**30:.3f} GiB, "
            f"peak {stats['peak_bytes_in_use'] / 2**30:.3f} GiB")


def _dense_modes(mode: str, params) -> "dict[str, str]":
    """How `dense`'s routing resolves `mode` for each projection weight of
    one layer and for the tied LM head, as the jitted steps route them."""
    import jax
    from repro.kernels.ops import resolve_dense_mode
    modes = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params["blocks"]):
        name = str(getattr(path[-1], "key", path[-1]))
        if name.startswith("w_"):
            modes[name] = resolve_dense_mode(mode, leaf[0])
    modes["lm_head"] = resolve_dense_mode(mode, params["embed"]["embedding"])
    return modes


def serve_phase(args, rehearsal: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import registry
    from repro.models import transformer as tf
    from repro.serving import ServeConfig, make_engine

    cfg = registry.get_config("qwen1.5-0.5b", smoke=rehearsal)
    mode = "interpret" if rehearsal else "auto"
    max_len = 256 if rehearsal else MAX_LEN
    lens = (8, 40) if rehearsal else PROMPT_LENS
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} seed={args.seed}")
    params = tf.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = make_engine(cfg, params, ServeConfig(
        slots=SLOTS, max_len=max_len, seed=args.seed, dense_kernel=mode,
        paged_attn_kernel=mode))
    kernel_mode = "interpret" if rehearsal else "kernel"
    paged_mode = "interpret" if rehearsal else "pallas"
    print(f"HBM after building the engine: {_hbm(jax.devices()[0])}")

    dense_modes = _dense_modes(mode, params)
    print(f"kernel modes: dense {mode} -> {sorted(set(dense_modes.values()))} "
          f"{dense_modes}; paged_attn {mode} -> {engine.paged_attn_mode}")
    _check(set(dense_modes.values()) == {kernel_mode},
           f"dense routing resolved to {dense_modes}")
    _check(engine.paged_attn_mode == paged_mode,
           f"paged attention resolved to {engine.paged_attn_mode}")

    # the decode step program must hold both Pallas kernels (lowered here
    # outside the engine, whose trace_counts stay untouched; interpret mode
    # lowers the kernels to plain ops)
    mb = engine.kv.cfg.max_blocks_per_seq
    text = jax.jit(lambda p, c, t, tb, pos, act: tf.decode_step_paged(
        p, engine.cfg, t, c, tb, pos, act)).lower(
        params, engine.caches, jnp.zeros((SLOTS, 1), jnp.int32),
        tuple(jnp.zeros((SLOTS, mb), jnp.int32) for _ in engine.kv.groups),
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool)).as_text()
    if not rehearsal:
        for kernel in ("_gpp_kernel", "_paged_attn_kernel"):
            _check(kernel in text, f"{kernel} is not in the decode step")
        print("decode step lowers with kernels: _gpp_kernel, "
              "_paged_attn_kernel")

    rng = np.random.default_rng(args.seed)
    # warm-up: one short request compiles the prefill-chunk and decode shapes
    t0 = time.perf_counter()
    engine.submit(rng.integers(0, cfg.vocab_size, lens[0]).tolist(),
                  max_new_tokens=2)
    engine.run()
    warm_s = time.perf_counter() - t0

    steps0 = len(engine.metrics)
    rids = [engine.submit(rng.integers(0, cfg.vocab_size,
                                       int(rng.integers(*lens))).tolist(),
                          max_new_tokens=MAX_NEW) for _ in range(SLOTS)]
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0
    steps = len(engine.metrics) - steps0
    produced = [len(results[r]) for r in rids]
    _check(produced == [MAX_NEW] * SLOTS, f"tokens per request {produced}")
    _check(all(0 <= t < cfg.vocab_size for r in rids for t in results[r]),
           "token id out of range")
    prefill = sum(m["prefill_tokens"] for m in engine.metrics[steps0:])
    decode = sum(m["decode_tokens"] for m in engine.metrics[steps0:])
    print(f"served {SLOTS} requests: {prefill} prefill tokens in chunks of "
          f"{engine.chunk}, {decode} decode tokens, {steps} engine steps")
    print(f"trace_counts: {engine.trace_counts}")
    _check(engine.trace_counts["prefill_chunk"] == 1
           and engine.trace_counts["decode"] == 1,
           f"step shapes retraced: {engine.trace_counts}")
    print(f"smoke timing (not a benchmark): warm-up incl. compile "
          f"{warm_s:.3f} s; {wall / steps * 1e3:.3f} ms per engine step "
          f"over {steps} steps")
    print(f"HBM after serving: {_hbm(jax.devices()[0])}")
    logits_check(args, cfg, params, rehearsal)


def logits_check(args, cfg, params, rehearsal: bool) -> None:
    """One prefill chunk per lane, then a few batched decode steps, through
    the kernel path and the ref path on the same pools and tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as tf

    kmodes = (("interpret", "interpret") if rehearsal
              else ("kernel", "pallas"))
    cfg_k = cfg.with_(dense_kernel=kmodes[0], paged_attn_kernel=kmodes[1])
    cfg_r = cfg.with_(dense_kernel="ref", paged_attn_kernel="ref")
    lanes, bs, chunk = 4, cfg.serve_block_size, 64
    mb = 256 // bs
    nb = lanes * mb + 1
    groups = len(tf.layer_group_keys(cfg))
    table = np.arange(1, nb, dtype=np.int32).reshape(lanes, mb)
    tables = tuple(jnp.asarray(table) for _ in range(groups))

    def steps(c):
        pre = jax.jit(lambda p, ca, t, row, s, last: tf.prefill_chunk(
            p, c, t, ca, row, s, last))
        dec = jax.jit(lambda p, ca, t, tb, pos, act: tf.decode_step_paged(
            p, c, t, ca, tb, pos, act))
        return pre, dec

    paths = {"kernel": steps(cfg_k), "ref": steps(cfg_r)}
    caches = {k: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              tf.paged_cache_specs(cfg, nb, bs))
              for k in paths}
    rng = np.random.default_rng(args.seed + 1)
    worst = scale = 0.0
    agree = total = 0

    def compare(lk, lr):
        nonlocal worst, scale, agree, total
        lk = np.asarray(lk, np.float32)
        lr = np.asarray(lr, np.float32)
        _check(np.isfinite(lk).all() and np.isfinite(lr).all(),
               "non-finite logits")
        worst = max(worst, float(np.abs(lk - lr).max()))
        scale = max(scale, float(np.abs(lr).max()))
        agree += int((lk.argmax(-1) == lr.argmax(-1)).sum())
        total += lk.shape[0]
        return lr

    last = np.zeros((lanes, 1), np.int32)
    for lane in range(lanes):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, chunk)),
                           jnp.int32)
        row = tuple(t[lane][None] for t in tables)
        out = {}
        for k, (pre, _) in paths.items():
            out[k], caches[k] = pre(params, caches[k], toks, row,
                                    jnp.int32(0), jnp.int32(chunk - 1))
        lr = compare(out["kernel"], out["ref"])
        last[lane, 0] = lr[0].argmax()
    positions = np.full((lanes,), chunk, np.int32)
    active = jnp.ones((lanes,), bool)
    for _ in range(CHECK_DECODE_STEPS):
        out = {}
        for k, (_, dec) in paths.items():
            out[k], caches[k] = dec(params, caches[k], jnp.asarray(last),
                                    tables, jnp.asarray(positions), active)
        lr = compare(out["kernel"][:, 0], out["ref"][:, 0])
        last[:, 0] = lr.argmax(-1)        # both paths get the same tokens
        positions += 1
    rel = worst / max(scale, 1e-30)
    print(f"kernel vs ref logits ({lanes} prefill chunks of {chunk} + "
          f"{CHECK_DECODE_STEPS} decode steps x {lanes} lanes): max |diff| "
          f"{worst!r}, max |ref| {scale!r}, relative {rel!r} (bound "
          f"{LOGITS_REL_BOUND}); argmax agreement {agree}/{total}")
    _check(rel <= LOGITS_REL_BOUND,
           f"kernel-vs-ref logits relative diff {rel} > {LOGITS_REL_BOUND}")


def mesh_phase(args, rehearsal: bool) -> None:
    """qwen1.5-0.5b train steps on a (2,2) data x model mesh of the four
    chips, against the same steps on a (1,1) mesh of one chip."""
    import jax
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.kernels.ops import resolve_dense_mode
    from repro.launch.steps import make_train_step
    from repro.models import registry
    from repro.models import transformer as tf
    from repro.optim import adamw

    cfg = registry.get_config("qwen1.5-0.5b", smoke=rehearsal)
    shape = ShapeConfig("smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    data = TokenPipeline(cfg, DataConfig(seed=args.seed, batch=TRAIN_BATCH,
                                         seq_len=TRAIN_SEQ))
    devices = jax.devices()
    losses = {}
    for mshape in ((1, 1), (2, 2)):
        devs = devices[:mshape[0] * mshape[1]]
        mesh = jax.make_mesh(mshape, ("data", "model"), devices=devs,
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            bundle = make_train_step(cfg, mesh, shape)
            params = tf.init_params(cfg, jax.random.PRNGKey(args.seed))
            w = params["blocks"]["b0"]["mlp"]["w_up"]
            print(f"mesh {mshape}: auto dense routing under the mesh -> "
                  f"{resolve_dense_mode('auto', w)}")
            params = jax.device_put(params, bundle.arg_shardings[0])
            opt = jax.device_put(adamw.adamw_init(params),
                                 bundle.arg_shardings[1])
            leaves = jax.tree.leaves(params)
            placed = set().union(*(l.sharding.device_set for l in leaves))
            _check(placed == set(devs), f"params placed on {placed}")
            split = sum(l.addressable_shards[0].data.shape != l.shape
                        for l in leaves)
            print(f"mesh {mshape}: params on devices "
                  f"{sorted(d.id for d in placed)}, {split}/{len(leaves)} "
                  f"leaves sharded")
            _check(mshape == (1, 1) or split > 0, "no param is sharded")
            run = []
            for step in range(TRAIN_STEPS):
                batch = {k: jax.device_put(v, bundle.arg_shardings[2][k])
                         for k, v in data.batch_at(step).items()}
                params, opt, metrics = bundle.fn(params, opt, batch,
                                                 jax.numpy.asarray(step))
                run.append(float(metrics["loss"]))
            _check(all(math.isfinite(x) for x in run), f"bad losses {run}")
            losses[mshape] = run
            del params, opt, batch, metrics
    diffs = [abs(a - b) / abs(b) for a, b in zip(losses[(2, 2)],
                                                 losses[(1, 1)])]
    print(f"train losses (1,1): {losses[(1, 1)]}")
    print(f"train losses (2,2): {losses[(2, 2)]}")
    print(f"max relative loss diff {max(diffs)!r} (bound {LOSS_REL_BOUND})")
    _check(max(diffs) <= LOSS_REL_BOUND,
           f"(2,2) vs (1,1) loss diff {max(diffs)} > {LOSS_REL_BOUND}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="smoke-width config, kernels in interpret mode, CPU")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal and args.chips == 4:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")

    import jax
    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if not args.cpu_rehearsal and dev.platform != "tpu":
        raise SmokeFailure(f"no TPU found (JAX sees {dev.platform}); "
                           "--cpu-rehearsal runs the smoke config on the CPU")
    if args.chips == 4 and len(devices) < 4:
        raise SmokeFailure(f"--chips 4 needs four devices, found "
                           f"{len(devices)} {dev.platform} device(s)")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {use_compile_cache()}")
    compile_s = _compile_seconds()

    if args.chips == 4:
        mesh_phase(args, args.cpu_rehearsal)
    else:
        serve_phase(args, args.cpu_rehearsal)
    print(f"compile seconds (backend compile or cache retrieval): "
          f"{compile_s():.3f}")
    print(f"HBM at exit: {_hbm(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
