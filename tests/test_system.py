"""End-to-end behaviour tests for the paper's system.

The paper's pipeline in one place: analytic design (Eqs 3-4) → schedule
construction → cycle-accurate execution → the same planner driving the JAX
streamer and the Pallas kernel's ring depth.  Plus a micro training run
proving the full stack (data → model → optimizer → checkpoint) descends.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.analytical as ana
from repro.core import schedule as sched
from repro.core import simulator as sim
from repro.core.analytical import PimConfig
from repro.core.schedule import plan_stream


class TestPaperPipelineEndToEnd:
    def test_design_to_execution(self):
        """Size an accelerator for a bandwidth budget (Eq 4), build the GPP
        schedule, execute it in the DES: bandwidth ~saturated, macros ~always
        busy, and throughput within ramp-overhead of the analytic optimum."""
        cfg = PimConfig(band=128.0, s=4.0).with_(n_in=24)  # t_pim:t_rw = 3:1
        n = round(ana.num_macros(cfg, "gpp"))
        rounds = 32
        res = sim.simulate("gpp", cfg, n, rounds)
        assert res.bandwidth_utilization > 0.95
        assert res.macro_utilization > 0.9
        ideal = rounds * (cfg.time_pim + cfg.time_rewrite)
        assert res.total_cycles < ideal * 1.1  # ramp only

    def test_planner_consistency_kernel_vs_streamer(self):
        """One planner (plan_stream) drives both levels: ring depth must be
        monotone in the transfer/compute ratio everywhere."""
        depths = [
            plan_stream(block_bytes=1e6, compute_flops=f,
                        flops_per_s=197e12, transfer_bytes_per_s=819e9).ring_depth
            for f in (1e4, 1e6, 1e8, 1e10)
        ]
        assert depths == sorted(depths, reverse=True)
        assert depths[-1] == 2  # compute-bound -> plain double buffering

    def test_schedule_ir_replays_in_simulator(self):
        """The idealized schedule's makespan matches the DES when bandwidth
        is unconstrained (the IR and the machine agree)."""
        cfg = PimConfig(band=1e9, s=4.0).with_(n_in=24)
        s = sched.build("gpp", cfg, 6, 5)
        r = sim.simulate("gpp", cfg, 6, 5)
        assert r.total_cycles == pytest.approx(s.makespan, rel=1e-6)


class TestTrainingEndToEnd:
    def test_micro_train_descends_and_resumes(self, tmp_path):
        """Full stack on CPU: synthetic pipeline -> reduced model -> AdamW ->
        checkpoint -> resume -> loss strictly below init."""
        from repro.checkpoint.manager import CheckpointManager
        from repro.data.pipeline import DataConfig, TokenPipeline
        from repro.models import registry
        from repro.models import transformer as tf
        from repro.optim import adamw

        cfg = registry.get_config("qwen1.5-0.5b", smoke=True)
        data = DataConfig(seed=0, batch=4, seq_len=32)
        pipe = TokenPipeline(cfg, data)
        params = tf.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = adamw.adamw_init(params)
        optc = adamw.AdamWConfig(lr=1e-3)

        @jax.jit
        def step(params, opt_state, batch):
            loss, g = jax.value_and_grad(
                lambda p: tf.loss_fn(p, cfg, batch))(params)
            params, opt_state, _ = adamw.adamw_update(optc, g, opt_state, params)
            return params, opt_state, loss

        losses = []
        for i in range(8):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

        mgr = CheckpointManager(str(tmp_path))
        mgr.save(8, {"p": params, "o": opt_state})
        restored, s8 = mgr.restore({"p": params, "o": opt_state})
        assert s8 == 8
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(8).items()}
        _, _, l1 = step(restored["p"], restored["o"], batch)
        _, _, l2 = step(params, opt_state, batch)
        assert float(l1) == pytest.approx(float(l2), rel=1e-6)


class TestKernelSystemIntegration:
    def test_kernel_ring_depth_from_paper_model(self):
        """The kernel's auto ring depth equals ceil(t_dma/t_cmp)+1 from the
        paper's timing model with TPU constants."""
        from repro.core.schedule import V5E
        from repro.kernels.ops import plan_ring_depth
        for M in (8, 64, 512):
            K = bn = 256
            t_dma = (K * bn * 2) / V5E.hbm_bytes_per_s
            t_cmp = (2 * M * K * bn) / V5E.peak_flops
            expect = min(8, max(2, math.ceil(t_dma / t_cmp) + 1))
            assert plan_ring_depth(M, K, bn) == expect

    def test_streamed_sequence_is_paper_workload(self):
        """The consecutive-GeMM BLAS workload (paper §V-A) through the
        streaming kernel, weights re-streamed per round."""
        from repro.kernels.ops import streamed_gemm_sequence
        from repro.kernels.ref import streamed_gemm_seq_ref
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (8, 128), jnp.float32)
        ws = jax.random.normal(key, (4, 128, 256), jnp.float32)
        ys = streamed_gemm_sequence(x, ws, block_n=128, num_bufs=3,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(ys),
                                   np.asarray(streamed_gemm_seq_ref(x, ws)),
                                   rtol=1e-5, atol=1e-4)
