"""Distributed-behaviour tests.

These need multiple devices, so each runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count set there — the main pytest
process keeps the default single device (smoke tests must not see 512).

Meshes are `jax.make_mesh(..., axis_types=Auto)` made ambient with
`jax.set_mesh`.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


class TestStreamerDistributed:
    def test_modes_match_and_emit_expected_collectives(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np, re
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro.core.streamer import stream_layers, StreamSettings
            from jax.sharding import AxisType

            mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            L, D, F, B = 6, 64, 128, 8
            key = jax.random.PRNGKey(0)
            ws = {"w1": jax.random.normal(key, (L, D, F)) * 0.05,
                  "w2": jax.random.normal(key, (L, F, D)) * 0.05}
            x = jax.random.normal(key, (B, D))
            shard = {"w1": P("data", None), "w2": P(None, "data")}
            full = {"w1": P(None, None), "w2": P(None, None)}
            ws_sh = {"w1": NamedSharding(mesh, P(None, "data", None)),
                     "w2": NamedSharding(mesh, P(None, None, "data"))}
            x_sh = NamedSharding(mesh, P("data", None))

            def apply_fn(x, w):
                return x + jnp.tanh(x @ w["w1"]) @ w["w2"]

            outs, ags = {}, {}
            with jax.set_mesh(mesh):
                for mode in ("resident", "insitu", "naive_pp", "gpp"):
                    f = jax.jit(lambda x, ws, m=mode: stream_layers(
                        apply_fn, x, ws, L,
                        settings=StreamSettings(mode=m, ring_depth=3),
                        mesh=mesh, shard_specs=shard, full_specs=full),
                        in_shardings=(x_sh, ws_sh))
                    outs[mode] = np.asarray(f(x, ws))
                    txt = f.lower(x, ws).compile().as_text()
                    ags[mode] = len(re.findall(r"all-gather", txt))
            for m in ("insitu", "naive_pp", "gpp"):
                np.testing.assert_allclose(outs[m], outs["resident"],
                                           rtol=1e-5, atol=1e-5)
            # gpp must emit chunked gathers: more, smaller all-gather ops
            assert ags["gpp"] > ags["naive_pp"] >= ags["insitu"] > 0, ags
            print("OK", ags)
        """)
        assert "OK" in out

    def test_gpp_training_gradients(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.core.streamer import stream_layers, StreamSettings
            from jax.sharding import AxisType
            mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            L, D, F, B = 5, 32, 64, 4
            key = jax.random.PRNGKey(1)
            ws = {"w": jax.random.normal(key, (L, D, D)) * 0.1}
            x = jax.random.normal(key, (B, D))
            shard = {"w": P("data", None)}
            full = {"w": P(None, None)}
            def apply_fn(c, w):
                return jnp.tanh(c @ w["w"])
            def loss(ws, mode):
                y = stream_layers(apply_fn, x, ws, L,
                                  settings=StreamSettings(mode=mode, ring_depth=4),
                                  mesh=mesh, shard_specs=shard, full_specs=full)
                return (y ** 2).mean()
            with jax.set_mesh(mesh):
                g_res = jax.jit(jax.grad(loss), static_argnums=1)(ws, "resident")
                g_gpp = jax.jit(jax.grad(loss), static_argnums=1)(ws, "gpp")
            np.testing.assert_allclose(np.asarray(g_gpp["w"]),
                                       np.asarray(g_res["w"]), rtol=1e-4, atol=1e-5)
            print("OK")
        """)
        assert "OK" in out


class TestContextParallelAttention:
    def test_cp_matches_reference_and_grads(self):
        """Heads not divisible by TP -> shard_map context parallelism must be
        numerically identical to the single-device path (incl. gradients)."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.models import attention as A
            from repro.models.layers import init_from_specs
            from jax.sharding import AxisType

            mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            cfg = A.AttnConfig(d_model=48, num_heads=6, num_kv_heads=2,
                               head_dim=8, dtype=jnp.float32)
            p = init_from_specs(A.attn_specs(cfg), jax.random.PRNGKey(0))
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 48)) * 0.5
            pos = jnp.broadcast_to(jnp.arange(64)[None], (4, 64))
            ref = A.gqa_forward(p, cfg, x, pos)
            with jax.set_mesh(mesh):
                outp = jax.jit(lambda p, x: A.gqa_forward(p, cfg, x, pos))(p, x)
            np.testing.assert_allclose(np.asarray(outp), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
            def loss(p, x):
                return (A.gqa_forward(p, cfg, x, pos) ** 2).mean()
            g_ref = jax.grad(loss)(p, x)
            with jax.set_mesh(mesh):
                g_cp = jax.jit(jax.grad(loss))(p, x)
            np.testing.assert_allclose(np.asarray(g_cp["w_q"]),
                                       np.asarray(g_ref["w_q"]),
                                       rtol=1e-3, atol=1e-4)
            print("OK")
        """)
        assert "OK" in out

    def test_cp_with_sliding_window(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.models import attention as A
            from repro.models.layers import init_from_specs
            from jax.sharding import AxisType
            mesh = jax.make_mesh((1, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            cfg = A.AttnConfig(d_model=24, num_heads=3, num_kv_heads=1,
                               head_dim=8, window=16, dtype=jnp.float32)
            p = init_from_specs(A.attn_specs(cfg), jax.random.PRNGKey(0))
            x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 24)) * 0.5
            pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
            ref = A.gqa_forward(p, cfg, x, pos)
            with jax.set_mesh(mesh):
                outp = jax.jit(lambda p, x: A.gqa_forward(p, cfg, x, pos))(p, x)
            np.testing.assert_allclose(np.asarray(outp), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
            print("OK")
        """, devices=4)
        assert "OK" in out


class TestMoEShardMap:
    def test_moe_shard_map_matches_local(self):
        """The explicit-schedule MoE (shard_map over data x model) must equal
        the local grouped-dispatch path, including gradients."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.models import moe as M
            from repro.models.layers import init_from_specs
            from jax.sharding import AxisType

            cfg = M.MoeConfig(d_model=32, d_ff=16, num_experts=8,
                              experts_per_token=2, capacity_factor=8.0,
                              dtype=jnp.float32, dispatch_groups=4)
            p = init_from_specs(M.moe_specs(cfg), jax.random.PRNGKey(0))
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32)) * 0.5

            ref = M.moe_apply(p, cfg, x)          # no mesh -> local path
            mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            wsh = {
                "router": NamedSharding(mesh, P(None, None)),
                "w_gate": NamedSharding(mesh, P("model", "data", None)),
                "w_up": NamedSharding(mesh, P("model", "data", None)),
                "w_down": NamedSharding(mesh, P("model", "data", None)),
            }
            psh = {k: wsh[k] for k in p}
            p_dev = jax.device_put(p, psh)
            x_dev = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            with jax.set_mesh(mesh):
                got = jax.jit(lambda p, x: M.moe_apply(p, cfg, x))(p_dev, x_dev)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)

            def loss(p, x):
                return (M.moe_apply(p, cfg, x) ** 2).mean()
            g_ref = jax.grad(loss)(p, x)
            with jax.set_mesh(mesh):
                g = jax.jit(jax.grad(loss))(p_dev, x_dev)
            for k in ("router", "w_gate", "w_down"):
                np.testing.assert_allclose(np.asarray(g[k]),
                                           np.asarray(g_ref[k]),
                                           rtol=1e-3, atol=1e-4)
            print("OK")
        """)
        assert "OK" in out


class TestStepsOnHostMesh:
    def test_train_step_lowers_and_runs(self):
        out = run_py("""
            import jax, jax.numpy as jnp
            from repro.configs.base import ShapeConfig
            from repro.launch.mesh import make_host_mesh
            from repro.launch.steps import make_train_step
            from repro.models import registry, transformer as tf
            from repro.optim import adamw

            cfg = registry.get_config("gemma3-12b", smoke=True)
            mesh = make_host_mesh(2, 2)
            shape = ShapeConfig("t", 64, 8, "train")
            with jax.set_mesh(mesh):
                b = make_train_step(cfg, mesh, shape)
                params = jax.device_put(tf.init_params(cfg, jax.random.PRNGKey(0)),
                                        b.arg_shardings[0])
                opt = jax.device_put(adamw.adamw_init(params), b.arg_shardings[1])
                import numpy as np
                batch = {"tokens": jnp.zeros((8, 64), jnp.int32),
                         "labels": jnp.ones((8, 64), jnp.int32)}
                batch = {k: jax.device_put(v, b.arg_shardings[2][k])
                         for k, v in batch.items()}
                params, opt, m = b.fn(params, opt, batch, jnp.asarray(0))
                assert np.isfinite(float(m["loss"]))
                print("OK", float(m["loss"]))
        """, devices=4)
        assert "OK" in out

    def test_decode_step_with_seq_sharded_cache(self):
        """long-context B=1 decode: cache must shard on sequence length."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ShapeConfig
            from repro.launch.mesh import make_host_mesh
            from repro.launch.steps import make_decode_step
            from repro.models import registry, transformer as tf

            cfg = registry.get_config("h2o-danube-1.8b", smoke=True)
            mesh = make_host_mesh(2, 2)
            shape = ShapeConfig("long", 64, 1, "decode")  # B=1 < dp size
            with jax.set_mesh(mesh):
                b = make_decode_step(cfg, mesh, shape)
                lowered = b.fn.lower(*b.input_specs)
                compiled = lowered.compile()
                print("OK", compiled.memory_analysis().temp_size_in_bytes)
        """, devices=4)
        assert "OK" in out

    def test_streaming_train_step_gpp_mode(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ShapeConfig
            from repro.core.streamer import StreamSettings
            from repro.launch.mesh import make_host_mesh
            from repro.launch.steps import make_train_step
            from repro.models import registry, transformer as tf
            from repro.optim import adamw

            cfg = registry.get_config("qwen2-7b", smoke=True).with_(
                stream=StreamSettings(mode="gpp", ring_depth=3))
            mesh = make_host_mesh(2, 2)
            shape = ShapeConfig("t", 64, 8, "train")
            with jax.set_mesh(mesh):
                b = make_train_step(cfg, mesh, shape)
                params = jax.device_put(tf.init_params(cfg, jax.random.PRNGKey(0)),
                                        b.arg_shardings[0])
                opt = jax.device_put(adamw.adamw_init(params), b.arg_shardings[1])
                batch = {"tokens": jnp.zeros((8, 64), jnp.int32),
                         "labels": jnp.ones((8, 64), jnp.int32)}
                batch = {k: jax.device_put(v, b.arg_shardings[2][k])
                         for k, v in batch.items()}
                params, opt, m = b.fn(params, opt, batch, jnp.asarray(0))
                assert np.isfinite(float(m["loss"]))
                print("OK")
        """, devices=4)
        assert "OK" in out


class TestTrainDriver:
    def test_cli_train_and_resume(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
               "qwen1.5-0.5b", "--smoke", "--batch", "4", "--seq", "32",
               "--devices", "4", "--mesh", "2x2",
               "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"]
        p1 = subprocess.run(cmd + ["--steps", "6"], capture_output=True,
                            text=True, timeout=600, env=env, cwd=REPO)
        assert p1.returncode == 0, p1.stderr[-2000:]
        p2 = subprocess.run(cmd + ["--steps", "9"], capture_output=True,
                            text=True, timeout=600, env=env, cwd=REPO)
        assert p2.returncode == 0, p2.stderr[-2000:]
        assert "resumed from step 6" in p2.stdout
