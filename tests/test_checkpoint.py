"""Checkpoint manager: atomicity, corruption detection, elastic resume."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import COMMITTED, CheckpointManager


def tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 4)), "b": jnp.ones((4,))},
        "opt": {"mu": jnp.zeros((8, 4)), "count": jnp.array(3, jnp.int32)},
    }


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        t = tree()
        mgr.save(10, t)
        restored, step = mgr.restore(t)
        assert step == 10
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_uncommitted_ignored(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, tree())
        mgr.save(2, tree(seed=2))
        # simulate crash mid-write of step 3: dir without commit marker
        os.makedirs(tmp_path / "step_3")
        assert mgr.latest_step() == 2

    def test_corruption_detected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(5, tree())
        shard = tmp_path / "step_5" / "shard_0.npz"
        data = shard.read_bytes()
        shard.write_bytes(data[:-8] + b"deadbeef")
        with pytest.raises(IOError, match="corrupt"):
            mgr.restore(tree())

    def test_gc_keeps_last_k(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, tree())
        assert mgr.all_steps() == [3, 4]

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(7, tree(), blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 7

    def test_shape_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, tree())
        bad = tree()
        bad["params"]["w"] = jnp.zeros((4, 4))
        with pytest.raises(ValueError, match="shape"):
            mgr.restore(bad)

    def test_elastic_resume_across_meshes(self, tmp_path):
        """Save under one sharding, restore onto a different mesh — the
        elastic-rescale story (device count changed between jobs).  Runs in a
        subprocess with 4 forced host devices."""
        import os
        import subprocess
        import sys
        import textwrap
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = os.path.join(repo, "src")
        code = textwrap.dedent(f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.checkpoint.manager import CheckpointManager
            from jax.sharding import AxisType
            mesh_a = jax.make_mesh((4, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            mesh_b = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            t = {{"w": jax.device_put(
                jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
                NamedSharding(mesh_a, P("data", None)))}}
            mgr = CheckpointManager({str(tmp_path)!r})
            mgr.save(1, t)
            shardings = {{"w": NamedSharding(mesh_b, P("data", "model"))}}
            restored, _ = mgr.restore(t, shardings=shardings)
            np.testing.assert_array_equal(
                np.asarray(restored["w"]),
                np.arange(32, dtype=np.float32).reshape(8, 4))
            assert restored["w"].sharding.mesh.shape["model"] == 2
            print("OK")
        """)
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=300,
                              env=env, cwd=repo)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "OK" in proc.stdout
