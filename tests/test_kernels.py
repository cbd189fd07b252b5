"""Pallas gpp_matmul (3-D tiled grid) vs pure-jnp oracle: shape/dtype sweeps,
fused-epilogue parity, ragged edges, and chunk-schedule properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

pytestmark = pytest.mark.tier1

from repro.core.schedule import V5E, plan_matmul_tiles
from repro.kernels.gpp_matmul import (
    _chunk_bounds, chunk_issue_schedule, gpp_matmul, gpp_matmul_grouped,
    plan_call, weight_pad_bytes,
)
from repro.kernels.ops import (
    dense, plan_ring_depth, streamed_gemm_sequence, streamed_matmul,
)
from repro.kernels.ref import (
    dense_grouped_ref, dense_ref, matmul_ref, streamed_gemm_seq_ref,
)

jax.config.update("jax_enable_x64", False)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


SHAPES = [
    (8, 256, 1024, 256),    # deep-ring regime (small M)
    (16, 512, 512, 128),
    (32, 128, 768, 256),
    (128, 256, 512, 512),   # single wide tile
    (8, 384, 1024, 128),    # K not divisible by chunks (remainder path)
]

# (M, K, N, block_m, block_n, block_k): every grid dim > 1, plus ragged edges
TILED_SHAPES = [
    (40, 300, 520, 16, 128, 128),   # ragged M, K and N
    (64, 512, 512, 32, 128, 128),   # clean 2x4x4 grid
    (16, 640, 384, 16, 128, 256),   # ragged K tile (640 = 2.5 * 256)
    (24, 128, 300, 8, 256, 128),    # ragged N < block_n on last tile
]


# (M, K, N) of each served bf16 weight at its benchmark cell's decode rows
SERVED_WEIGHTS = {
    "qwen2-7b q/o": (16, 3584, 3584),
    "qwen2-7b k/v": (16, 3584, 512),
    "qwen2-7b gate/up": (16, 3584, 18944),
    "qwen2-7b down": (16, 18944, 3584),
    "qwen1.5-0.5b q/k/v/o": (4, 1024, 1024),
    "qwen1.5-0.5b gate/up": (4, 1024, 2816),
    "qwen1.5-0.5b down": (4, 2816, 1024),
    "deepseek-v2-lite routed gate/up": (32, 2048, 1408),
    "deepseek-v2-lite routed down": (32, 1408, 2048),
    "deepseek-v2-lite shared down": (32, 2816, 2048),
    "deepseek-v2-lite dense down": (32, 10944, 2048),
    "deepseek-v2-lite w_dkv": (32, 2048, 576),
}


class TestNumerics:
    @pytest.mark.parametrize("M,K,N,bn", SHAPES)
    @pytest.mark.parametrize("G", [1, 2, 4])
    def test_matches_oracle_f32(self, M, K, N, bn, G):
        k1, k2 = jax.random.split(jax.random.PRNGKey(M * N + G))
        x, w = rand(k1, (M, K), jnp.float32), rand(k2, (K, N), jnp.float32)
        y = gpp_matmul(x, w, block_n=bn, num_bufs=G, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(matmul_ref(x, w)),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("M,K,N,bm,bn,bk", TILED_SHAPES)
    @pytest.mark.parametrize("G", [1, 2, 4])
    def test_3d_grid_matches_oracle(self, M, K, N, bm, bn, bk, G):
        """Parity on the full 3-D (m, n, k) grid incl. ragged final tiles."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(M + K + N + G))
        x, w = rand(k1, (M, K), jnp.float32), rand(k2, (K, N), jnp.float32)
        y = gpp_matmul(x, w, block_m=bm, block_n=bn, block_k=bk,
                       num_bufs=G, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(matmul_ref(x, w)),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("dtype,rtol,atol", [
        (jnp.bfloat16, 3e-2, 0.5), (jnp.float32, 1e-5, 1e-4),
    ])
    @pytest.mark.parametrize("G", [1, 2, 4])
    def test_dtype_streaming(self, dtype, rtol, atol, G):
        """bf16/f32 weights DMA'd raw, accumulated in f32, across ring depths
        and a ragged multi-tile K."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(7))
        x, w = rand(k1, (16, 320), dtype), rand(k2, (320, 512), dtype)
        y = gpp_matmul(x, w, block_m=16, block_n=128, block_k=128,
                       num_bufs=G, interpret=True)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(matmul_ref(x, w), np.float32),
                                   rtol=rtol, atol=atol)

    @pytest.mark.parametrize("G", [1, 2, 4])
    def test_int8_weight_streaming(self, G):
        """int8 weights stream raw through the ring and dequantize in-kernel
        against the f32 accumulator via the per-column epilogue scale."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(11))
        x = rand(k1, (16, 320), jnp.float32)
        w = jax.random.randint(k2, (320, 520), -127, 127, jnp.int8)
        scale = jnp.abs(rand(k2, (520,), jnp.float32)) * 0.02 + 1e-3
        y = gpp_matmul(x, w, w_scale=scale, block_m=16, block_n=128,
                       block_k=128, num_bufs=G, interpret=True)
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(dense_ref(x, w, w_scale=scale)),
            rtol=1e-5, atol=1e-3)

    @pytest.mark.parametrize("act", [None, "relu", "gelu", "silu"])
    def test_fused_epilogue_bias_activation(self, act):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(13), 3)
        x, w = rand(k1, (24, 256), jnp.float32), rand(k2, (256, 384), jnp.float32)
        b = rand(k3, (384,), jnp.float32)
        y = gpp_matmul(x, w, bias=b, activation=act, block_m=8, block_n=128,
                       block_k=128, num_bufs=3, interpret=True)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(dense_ref(x, w, bias=b, activation=act)),
            rtol=1e-5, atol=1e-4)

    @given(st.integers(1, 6), st.integers(1, 8))
    @settings(max_examples=12, deadline=None)
    def test_strategy_invariance(self, G, seed):
        """All ring depths compute the same function (schedule is semantics-free)."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        x, w = rand(k1, (8, 128), jnp.float32), rand(k2, (128, 512), jnp.float32)
        y = gpp_matmul(x, w, block_n=128, num_bufs=G, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(matmul_ref(x, w)),
                                   rtol=1e-5, atol=1e-4)

    def test_sequence_matches_oracle(self):
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        x = rand(k1, (8, 256), jnp.float32)
        ws = rand(k2, (5, 256, 512), jnp.float32)
        ys = streamed_gemm_sequence(x, ws, block_n=128, num_bufs=4, interpret=True)
        np.testing.assert_allclose(np.asarray(ys),
                                   np.asarray(streamed_gemm_seq_ref(x, ws)),
                                   rtol=1e-5, atol=1e-4)

    def test_ragged_n_no_longer_errors(self):
        """N % block_n != 0 pads the last ragged tile instead of raising."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(5))
        x, w = rand(k1, (8, 128), jnp.float32), rand(k2, (128, 300), jnp.float32)
        y = gpp_matmul(x, w, block_n=256, num_bufs=2, interpret=True)
        assert y.shape == (8, 300)
        np.testing.assert_allclose(np.asarray(y), np.asarray(matmul_ref(x, w)),
                                   rtol=1e-5, atol=1e-4)

    def test_tiny_k_no_longer_errors(self):
        """K < chunks clamps the chunk count instead of raising."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(6))
        x, w = rand(k1, (8, 2), jnp.float32), rand(k2, (2, 256), jnp.float32)
        y = gpp_matmul(x, w, block_n=128, num_bufs=8, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(matmul_ref(x, w)),
                                   rtol=1e-5, atol=1e-4)

    def test_vmem_exceeding_shape_now_tiles(self):
        """A shape whose naive working set busts the old ~100 MiB ceiling
        (whole-K ring + whole-M activations resident) runs via M/K tiling."""
        M, K, N = 256, 8192, 2048
        # the old 1-D kernel's configuration: whole K and M resident
        with pytest.raises(ValueError, match="VMEM"):
            plan_matmul_tiles(M, K, N, block_m=M, block_k=K, block_n=2048,
                              num_bufs=4)
        k1, k2 = jax.random.split(jax.random.PRNGKey(9))
        x, w = rand(k1, (M, K), jnp.float32), rand(k2, (K, N), jnp.float32)
        y = gpp_matmul(x, w, interpret=True)  # auto-planned tiles
        np.testing.assert_allclose(np.asarray(y), np.asarray(matmul_ref(x, w)),
                                   rtol=1e-5, atol=2e-3)

    @pytest.mark.parametrize("E,M,K,N", [
        (0, 8, 11 * 128, 37 * 128),     # flat: DeepSeek x qwen2 d_ff
        (2, 8, 37 * 128, 11 * 128),     # grouped: qwen2 d_ff x DeepSeek
    ], ids=["gpp_matmul", "gpp_matmul_grouped"])
    def test_divisor_tiles_match_oracle(self, E, M, K, N):
        """Weights with the served shapes' factors (a prime number of
        128-lane tiles) run on planned tiles that divide them, with no pad,
        and match the oracle."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(K + N))
        lead = (E,) if E else ()
        x = rand(k1, (*lead, M, K), jnp.float32)
        w = rand(k2, (*lead, K, N), jnp.float32)
        plan, padded = plan_call(M, K, N, jnp.float32, jnp.float32)
        assert padded == (M, K, N)
        assert weight_pad_bytes(M, K, N, jnp.float32, jnp.float32) == 0
        assert plan.grid(M, N, K)[1:] != (1, 1)     # more than one tile
        if E:
            got = gpp_matmul_grouped(x, w, interpret=True)
            want = dense_grouped_ref(x, w)
        else:
            got = gpp_matmul(x, w, interpret=True)
            want = matmul_ref(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=2e-3)

    def test_dense_kernel_path_is_differentiable(self):
        """Training goes through dense(mode=auto->kernel) on TPU: the kernel
        path carries a custom_vjp (ref-math backward), so grads must exist
        and match the ref route."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(19), 3)
        x = rand(k1, (4, 128), jnp.float32)
        w = rand(k2, (128, 256), jnp.float32) * 0.05
        b = rand(k3, (256,), jnp.float32) * 0.1

        def loss(mode):
            def f(x, w, b):
                y = dense(x, w, bias=b, activation="silu", mode=mode)
                return jnp.sum(y * y)
            return f

        gk = jax.grad(loss("interpret"), argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(loss("ref"), argnums=(0, 1, 2))(x, w, b)
        for a, r in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)

    def test_dense_routes_and_matches(self):
        """dense() ref/interpret routes agree on leading-dim inputs."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(17), 3)
        x = rand(k1, (2, 6, 256), jnp.float32)
        w = rand(k2, (256, 384), jnp.float32)
        b = rand(k3, (384,), jnp.float32)
        y_ref = dense(x, w, bias=b, activation="silu", mode="ref")
        y_krn = dense(x, w, bias=b, activation="silu", mode="interpret")
        assert y_ref.shape == y_krn.shape == (2, 6, 384)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_krn),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("scale_shape", ["scalar", "per_expert", "per_col"])
    def test_grouped_int8_dequant_matches_flat_epilogue(self, scale_shape):
        """int8 `w_scale` dequant for the grouped kernel: per-expert scales
        fold into the fused epilogue and reproduce the FLAT kernel's dequant
        path expert-by-expert (plus the grouped oracle)."""
        from repro.kernels.gpp_matmul import gpp_matmul_grouped
        from repro.kernels.ref import dense_grouped_ref
        E, C, D, F = 3, 13, 64, 96
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(23), 3)
        x = rand(k1, (E, C, D), jnp.float32)
        w = jax.random.randint(k2, (E, D, F), -127, 127, jnp.int8)
        full = jnp.abs(rand(k3, (E, F), jnp.float32)) * 0.02 + 1e-3
        scale = {"scalar": full[0, 0], "per_expert": full[:, 0],
                 "per_col": full}[scale_shape]
        y = gpp_matmul_grouped(x, w, w_scale=scale, activation="silu",
                               interpret=True)
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(dense_grouped_ref(x, w, w_scale=scale,
                                         activation="silu")),
            rtol=1e-5, atol=1e-3)
        if scale_shape == "per_col":
            for e in range(E):
                flat = gpp_matmul(x[e], w[e], w_scale=scale[e],
                                  activation="silu", interpret=True)
                np.testing.assert_allclose(np.asarray(y[e]), np.asarray(flat),
                                           rtol=1e-5, atol=1e-3)

    def test_grouped_dequant_ref_mode_and_grads(self):
        """dense_grouped(mode="ref") pre-scales like dense()'s ref path, and
        the kernel path stays differentiable with a scale attached."""
        from repro.kernels.ops import dense_grouped
        from repro.kernels.ref import dense_grouped_ref
        E, C, D, F = 2, 8, 32, 48
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(29), 3)
        x = rand(k1, (E, C, D), jnp.float32)
        w = rand(k2, (E, D, F), jnp.float32) * 0.05
        scale = jnp.abs(rand(k3, (E, F), jnp.float32)) + 0.5
        y_ref = dense_grouped(x, w, w_scale=scale, mode="ref")
        y_krn = dense_grouped(x, w, w_scale=scale, mode="interpret")
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_krn),
                                   rtol=1e-4, atol=1e-4)

        def loss(mode):
            def f(x, w):
                y = dense_grouped(x, w, w_scale=scale, activation="silu",
                                  mode=mode)
                return jnp.sum(y * y)
            return f

        gk = jax.grad(loss("interpret"), argnums=(0, 1))(x, w)
        gr = jax.grad(loss("ref"), argnums=(0, 1))(x, w)
        for a, r in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)


class TestPlanner:
    def test_respects_budget(self):
        from repro.core.schedule import matmul_vmem_bytes
        plan = plan_matmul_tiles(4096, 16384, 32768, x_itemsize=2,
                                 w_itemsize=2, out_itemsize=2)
        assert plan.vmem_bytes <= 100 * 1024 * 1024
        assert plan.vmem_bytes == matmul_vmem_bytes(
            plan.block_m, plan.block_n, plan.block_k, plan.num_bufs,
            x_itemsize=2, w_itemsize=2, out_itemsize=2)

    def test_pinned_dims_honored(self):
        plan = plan_matmul_tiles(512, 4096, 4096, block_n=512, num_bufs=3)
        assert plan.block_n == 512 and plan.num_bufs == 3

    def test_small_shapes_single_tile(self):
        plan = plan_matmul_tiles(8, 256, 256)
        assert plan.block_m >= 8 and plan.block_k >= 256 and plan.block_n >= 256
        assert plan.grid(8, 256, 256) == (1, 1, 1)

    def test_pinned_overflow_raises(self):
        with pytest.raises(ValueError, match="VMEM"):
            plan_matmul_tiles(8192, 8192, 8192, block_m=8192, block_k=8192,
                              block_n=8192, num_bufs=2)

    def test_planner_regimes(self):
        """Paper's insight in kernel form: DMA-bound (small n_in=M) needs a
        deep ring; compute-bound (large M) degenerates to double buffering."""
        assert plan_ring_depth(8, 256, 256) >= 4
        assert plan_ring_depth(1024, 256, 256) == 2

    @pytest.mark.parametrize("M,K,N", SERVED_WEIGHTS.values(),
                             ids=list(SERVED_WEIGHTS))
    def test_free_tiles_divide_served_weights(self, M, K, N):
        """A free plan streams each served bf16 weight as it lies in HBM:
        block_k divides K and block_n divides N (a lane dim that is no
        128-lane multiple, MLA's latent projection of 512 + 64, pads only
        to the next whole lane), the working set fits the budget, and a
        weight tile moves at least 512 KiB."""
        bf16 = jnp.bfloat16
        plan, (_, Kp, Np) = plan_call(M, K, N, bf16, bf16)
        assert (Kp, Np) == (K, -(-N // 128) * 128)
        assert Kp % plan.block_k == 0 and Np % plan.block_n == 0
        assert plan.vmem_bytes <= V5E.vmem_limit_bytes
        assert plan.block_k * plan.block_n * 2 >= 512 * 1024
        assert weight_pad_bytes(M, K, N, bf16, bf16) == (
            0 if N % 128 == 0 else K * Np * 2)

    def test_pinned_tile_that_does_not_divide_still_pads(self):
        """DeepSeek-V2-Lite's routed gate/up (1408 = 11 x 128 columns) at a
        pinned block_n of 256, the tile planned before divisor tiles: the
        wrapper pads N to 1536 (six tiles), as pinned dims always did."""
        bf16 = jnp.bfloat16
        plan, (_, Kp, Np) = plan_call(32, 2048, 1408, bf16, bf16,
                                      block_n=256)
        assert plan.block_n == 256 and (Kp, Np) == (2048, 1536)
        free, (_, _, Np_free) = plan_call(32, 2048, 1408, bf16, bf16)
        assert Np_free == 1408 and 1408 % free.block_n == 0


class TestSchedule:
    def test_chunk_schedule_covers_every_chunk_once(self):
        """Replay the kernel's issue schedule symbolically on the flattened
        3-D grid: every (step, chunk) must be DMA'd exactly once, at or
        before the step that computes on it — including across n/k/m
        tile-loop boundaries and short (sub-ramp) grids."""
        for G, C in [(1, 1), (2, 1), (3, 2), (4, 3), (6, 5),
                     # C capped below G-1 by whole sublane tiles
                     (4, 1), (6, 2), (8, 4)]:
            for grid in [(1, 1, 1), (1, 2, 3), (2, 3, 2), (1, G, 1),
                         (3, 1, 1), (2, 2, G + 2)]:
                S = grid[0] * grid[1] * grid[2]
                issued = chunk_issue_schedule(S, G, C)
                for s in range(S):
                    for c in range(C):
                        steps = issued.get((s, c), [])
                        assert len(steps) == 1, (G, grid, s, c, steps)
                        assert steps[0] <= s, "chunk must arrive before compute"
                extra = set(issued) - {(s, c) for s in range(S) for c in range(C)}
                assert not extra, (G, grid, extra)

    def test_chunk_bounds_partition(self):
        for K in (128, 384, 1000):
            for chunks in (1, 2, 3, 5, 7):
                if K < chunks:
                    continue
                spans = [_chunk_bounds(K, chunks, c) for c in range(chunks)]
                assert spans[0][0] == 0 and spans[-1][1] == K
                for (a, b), (c, d) in zip(spans, spans[1:]):
                    assert b == c

    def test_ring_chunks_are_whole_sublane_tiles(self):
        """Mosaic DMAs slice rows in whole (sublane, 128-lane) tiles: every
        chunk of a tile-multiple block is non-empty and tile-aligned."""
        from repro.kernels.gpp_matmul import ring_chunks, sublane_tile
        assert [sublane_tile(n) for n in (4, 2, 1)] == [8, 16, 32]
        for itemsize in (4, 2, 1):
            tile = sublane_tile(itemsize)
            for rows in (tile, 2 * tile, 128, 1024, 2048):
                for G in range(1, 9):
                    C = ring_chunks(G, rows, tile)
                    assert 1 <= C <= max(1, G - 1)
                    spans = [_chunk_bounds(rows, C, c, tile)
                             for c in range(C)]
                    assert spans[0][0] == 0 and spans[-1][1] == rows
                    for lo, hi in spans:
                        assert hi > lo and lo % tile == 0 and hi % tile == 0
        # a block that is not a tile multiple moves whole
        assert ring_chunks(8, 24, 16) == 1 and ring_chunks(8, 16, 32) == 1

    def test_flat_bandwidth_bytes_per_step(self):
        """Steady-state issued bytes per grid step == exactly one tile, even
        across the n->n+1 and m->m+1 tile-loop boundaries."""
        G, bk, bn = 4, 384, 128
        C = G - 1
        grid = (2, 3, 2)                     # (num_m, num_n, num_k)
        S = grid[0] * grid[1] * grid[2]
        issued = chunk_issue_schedule(S, G, C)
        per_step = [0] * S
        for (step, c), at in issued.items():
            lo, hi = _chunk_bounds(bk, C, c)
            per_step[at[0]] += (hi - lo) * bn
        tile = bk * bn
        # steady-state steps (past ramp, before drain) move exactly one tile
        for j in range(1, S - C):
            assert per_step[j] == tile, (j, per_step[j], tile)
        # the ramp step must burst (pipeline fill), the drain steps taper
        assert per_step[0] > tile
        assert sum(per_step) == S * tile
