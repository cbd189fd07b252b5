"""End-to-end training driver.

CPU/examples:  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \\
                   --smoke --steps 20 --batch 8 --seq 128 --devices 4
Fleet:         the same entry point under jax.distributed (one process per
               host); the mesh comes from make_production_mesh() and the data
               pipeline shards by host id.

Fault tolerance in the loop: deterministic data (seed, step), async atomic
checkpoints every --ckpt-every steps, automatic resume from the latest
committed step, straggler watchdog on step wall-times.
"""
from __future__ import annotations

import argparse
import collections
import os
import statistics
import time


class StepWatchdog:
    """Straggler detector: `record(dt)` is True when a step's wall time
    exceeds `factor` x the median of recent steps (a bounded window, so the
    hot loop stays O(window) however long the run)."""

    def __init__(self, factor: float = 3.0, window: int = 256):
        self.factor = factor
        self._times = collections.deque(maxlen=window)

    @property
    def median(self) -> float:
        return statistics.median(self._times) if self._times else 0.0

    def record(self, dt: float) -> bool:
        straggled = (len(self._times) >= 3
                     and dt > self.factor * self.median)
        self._times.append(dt)
        return straggled


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU dev-mode); 0 = as-is")
    ap.add_argument("--mesh", default="auto",
                    help="'auto' | 'DxM' e.g. 4x2 | 'production'")
    ap.add_argument("--stream-mode", default=None,
                    choices=["resident", "insitu", "naive_pp", "gpp"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs.base import ShapeConfig
    from repro.core.streamer import StreamSettings
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.launch.steps import make_train_step
    from repro.models import registry
    from repro.models import transformer as tf
    from repro.optim import adafactor as adaf
    from repro.optim import adamw as adam

    use_compile_cache()
    cfg = registry.get_config(args.arch, smoke=args.smoke)
    if args.stream_mode:
        cfg = cfg.with_(stream=StreamSettings(mode=args.stream_mode,
                                              ring_depth=cfg.stream.ring_depth))

    if args.mesh == "production":
        mesh = make_production_mesh()
    elif args.mesh == "auto":
        n = len(jax.devices())
        d = max(1, n // 2)
        mesh = make_host_mesh(d, n // d)
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh(d, m)
    print(f"mesh: {dict(mesh.shape)}  devices: {len(jax.devices())}")

    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    with jax.set_mesh(mesh):
        bundle = make_train_step(cfg, mesh, shape)

        key = jax.random.PRNGKey(0)
        params = tf.init_params(cfg, key)
        params = jax.device_put(params, bundle.arg_shardings[0])
        if cfg.optimizer == "adafactor":
            opt_state = adaf.adafactor_init(params)
        else:
            opt_state = adam.adamw_init(params)
        opt_state = jax.device_put(opt_state, bundle.arg_shardings[1])

        start_step = 0
        mgr = None
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir)
            if mgr.latest_step() is not None:
                state, start_step = mgr.restore(
                    {"params": params, "opt": opt_state},
                    shardings={"params": bundle.arg_shardings[0],
                               "opt": bundle.arg_shardings[1]})
                params, opt_state = state["params"], state["opt"]
                print(f"resumed from step {start_step}")

        pipe = TokenPipeline(cfg, DataConfig(
            seed=1234, batch=args.batch, seq_len=args.seq)).start(start_step)
        watchdog = StepWatchdog()
        losses = []
        try:
            for step in range(start_step, args.steps):
                batch_np = next(pipe)
                batch = {k: jax.device_put(v, bundle.arg_shardings[2][k])
                         for k, v in batch_np.items()}
                t0 = time.time()
                params, opt_state, metrics = bundle.fn(
                    params, opt_state, batch, jax.numpy.asarray(step))
                loss = float(metrics["loss"])
                dt = time.time() - t0
                losses.append(loss)
                if watchdog.record(dt):
                    print(f"[watchdog] step {step} straggled: {dt:.2f}s "
                          f"(median {watchdog.median:.2f}s)")
                if step % args.log_every == 0:
                    print(f"step {step:5d} loss {loss:8.4f} "
                          f"gnorm {float(metrics['grad_norm']):8.3f} {dt*1e3:7.1f} ms")
                if mgr and step and step % args.ckpt_every == 0:
                    mgr.save(step, {"params": params, "opt": opt_state},
                             blocking=False)
        finally:
            pipe.stop()
            if mgr:
                mgr.wait()

        if mgr:
            mgr.save(args.steps, {"params": params, "opt": opt_state})
        print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
        return losses


if __name__ == "__main__":
    main()
