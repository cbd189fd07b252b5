"""Pallas paged-attention kernel (interpret mode): logits-level parity with
the gather+_sdpa read path across GQA, MLA, and sliding-window attention, in
both prefill-chunk and decode — including ragged last blocks, inactive lanes
parked on null block 0, heterogeneous decode positions, and the ring-depth
planner feeding it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.schedule import (TimingCache, plan_paged_attn,
                                 set_default_timing_cache)
from repro.kernels.ops import paged_attn, resolve_paged_attn_mode
from repro.models import attention as A
from repro.models import registry
from repro.models import transformer as tf
from repro.models.layers import init_from_specs

pytestmark = pytest.mark.tier1

GQA = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
           dtype=jnp.float32)
WINDOW = dict(GQA, window=16)
MLA = dict(d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
           kv_lora_rank=32, rope_head_dim=8, dtype=jnp.float32)
CFGS = {"gqa": GQA, "window": WINDOW, "mla": MLA}


def _attn_cfg(name, mode):
    return A.AttnConfig(**{**CFGS[name], "paged_mode": mode})


def _pools(c, nb, bs, seed=1):
    key = jax.random.PRNGKey(seed)
    return {k: jax.random.normal(key, s.shape, s.dtype) * 0.3
            for k, s in A.paged_cache_specs(c, nb, bs).items()}


# ---------------------------------------------------------------------------
# op-level parity: kernels.ops.paged_attn interpret vs ref
# ---------------------------------------------------------------------------

class TestOpParity:
    def test_gqa_heterogeneous_positions_and_ragged_tails(self):
        """Lanes at unaligned positions (ragged last blocks), one lane with a
        short context, tables in scrambled physical order."""
        c = _attn_cfg("gqa", "auto")
        nb, bs, MB, B = 11, 8, 4, 3
        pools = _pools(c, nb, bs)
        tables = jnp.asarray([[7, 2, 9, 4], [1, 5, 0, 0], [3, 6, 8, 10]],
                             jnp.int32)
        positions = jnp.asarray([26, 9, 31], jnp.int32)   # ragged, full
        q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, 4, 16))
        kw = dict(num_kv_heads=2, scale=0.25)
        ref = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="ref", **kw)
        got = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="interpret", **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_inactive_lane_parked_on_null_block(self):
        """A lane whose table is all zeros (never mapped / preempted) must
        not produce NaN/Inf — its rows read the null block and are fully
        position-masked except slot 0."""
        c = _attn_cfg("gqa", "auto")
        pools = _pools(c, 5, 8)
        tables = jnp.asarray([[1, 2, 0, 0], [0, 0, 0, 0]], jnp.int32)
        positions = jnp.asarray([12, 0], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 4, 16))
        kw = dict(num_kv_heads=2, scale=0.25)
        ref = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="ref", **kw)
        got = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="interpret", **kw)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_window_expiry_masked_per_block(self):
        c = _attn_cfg("window", "auto")
        pools = _pools(c, 9, 8)
        tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        positions = jnp.asarray([29], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 4, 16))
        kw = dict(num_kv_heads=2, scale=0.25, window=16)
        ref = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="ref", **kw)
        got = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="interpret", **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_prefill_chunk_queries(self):
        """S > 1 block-aligned chunk: causal within the chunk + full prefix."""
        c = _attn_cfg("gqa", "auto")
        pools = _pools(c, 9, 8)
        tables = jnp.asarray([[5, 1, 4, 2]], jnp.int32)
        positions = jnp.asarray([16], jnp.int32)          # chunk 3 of 4
        q = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 4, 16))
        kw = dict(num_kv_heads=2, scale=0.25)
        ref = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="ref", **kw)
        got = paged_attn(q, pools["k"], pools["v"], tables, positions,
                         mode="interpret", **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_mla_latent_mqa_form(self):
        c = _attn_cfg("mla", "auto")
        pools = _pools(c, 7, 8)
        tables = jnp.asarray([[2, 4, 1, 6], [3, 5, 0, 0]], jnp.int32)
        positions = jnp.asarray([25, 10], jnp.int32)
        dk = 32 + 8                                       # kv_lora + rope
        q = jax.random.normal(jax.random.PRNGKey(6), (2, 1, 4, dk))
        kw = dict(num_kv_heads=1, scale=0.2, mla=True)
        ref = paged_attn(q, pools["c_kv"], pools["k_rope"], tables, positions,
                         mode="ref", **kw)
        got = paged_attn(q, pools["c_kv"], pools["k_rope"], tables, positions,
                         mode="interpret", **kw)
        assert ref.shape == (2, 1, 4, 32)                 # latent-space output
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_ring_depths_agree(self):
        """G = 1 (in-situ), 2 (naive ping-pong), 4 (GPP) all reproduce the
        same output — the ring depth is a throughput knob, not semantics."""
        c = _attn_cfg("gqa", "auto")
        pools = _pools(c, 9, 8)
        tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        positions = jnp.asarray([31, 17], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(7), (2, 1, 4, 16))
        from repro.kernels.paged_attention import paged_attention
        outs = [paged_attention(q, pools["k"], pools["v"], tables, positions,
                                num_kv_heads=2, scale=0.25, num_bufs=G,
                                interpret=True) for G in (1, 2, 4)]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("mode", ("ref", "interpret"))
    def test_four_dim_pools_read_as_the_lane_dense_layout(self, mode):
        """The model stores GQA pools lane-dense, (nb, bs, KVH*hd); pools
        built as (nb, bs, KVH, hd) are still accepted and read exactly as
        the flat pools holding the same rows."""
        c = _attn_cfg("gqa", "auto")
        pools = _pools(c, 11, 8)
        assert pools["k"].shape == pools["v"].shape == (11, 8, 2 * 16)
        four = {k: p.reshape(11, 8, 2, 16) for k, p in pools.items()}
        tables = jnp.asarray([[7, 2, 9, 4], [1, 5, 0, 0]], jnp.int32)
        positions = jnp.asarray([26, 9], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(10), (2, 3, 4, 16))
        kw = dict(num_kv_heads=2, scale=0.25, mode=mode)
        flat = paged_attn(q, pools["k"], pools["v"], tables, positions, **kw)
        got = paged_attn(q, four["k"], four["v"], tables, positions, **kw)
        assert flat.shape == (2, 3, 4, 16)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(flat))

    def test_mode_validation(self):
        with pytest.raises(ValueError):
            resolve_paged_attn_mode("bogus")
        assert resolve_paged_attn_mode("ref") == "ref"
        assert resolve_paged_attn_mode("pallas") == "pallas"


# ---------------------------------------------------------------------------
# attention-level parity: the *_paged model fns under the cfg knob
# ---------------------------------------------------------------------------

class TestAttentionLevelParity:
    @pytest.mark.parametrize("name", ("gqa", "window", "mla"))
    def test_decode_paged(self, name):
        cref, cker = _attn_cfg(name, "ref"), _attn_cfg(name, "interpret")
        p = init_from_specs(A.attn_specs(cref), jax.random.PRNGKey(0))
        pools = _pools(cref, 9, 8)
        tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
        positions = jnp.asarray([27, 11], jnp.int32)
        active = jnp.asarray([True, True])
        x = jax.random.normal(jax.random.PRNGKey(8), (2, 1, 64)) * 0.5
        fn = A.mla_decode_paged if cref.is_mla else A.gqa_decode_paged
        ref, cache_r = fn(p, cref, x, pools, tables, positions, active)
        got, cache_k = fn(p, cker, x, pools, tables, positions, active)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=1e-4, atol=1e-4)
        # the write path is shared: caches must be bit-identical
        for kk in cache_r:
            np.testing.assert_array_equal(np.asarray(cache_r[kk]),
                                          np.asarray(cache_k[kk]))

    @pytest.mark.parametrize("name", ("gqa", "window", "mla"))
    def test_prefill_chunk_paged(self, name):
        cref, cker = _attn_cfg(name, "ref"), _attn_cfg(name, "interpret")
        p = init_from_specs(A.attn_specs(cref), jax.random.PRNGKey(0))
        pools = _pools(cref, 9, 8)
        table_row = jnp.asarray([[3, 1, 4, 2]], jnp.int32)
        x = jax.random.normal(jax.random.PRNGKey(9), (1, 16, 64)) * 0.5
        fn = A.mla_prefill_paged if cref.is_mla else A.gqa_prefill_paged
        ref, _ = fn(p, cref, x, pools, table_row, 8)
        got, _ = fn(p, cker, x, pools, table_row, 8)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=1e-4, atol=1e-4)


class TestPoolWrites:
    @pytest.mark.parametrize("write", ("span", "token", "multi"))
    def test_token_rows_land_flattened(self, write):
        """Each write helper stores a token's (KVH, hd) row as one lane-dense
        pool row: the same bytes a (nb, bs, KVH, hd) pool would hold."""
        c = _attn_cfg("gqa", "ref")
        flat = _pools(c, 9, 8)["k"]
        four = flat.reshape(9, 8, 2, 16)
        tables = jnp.asarray([[3, 1, 4, 2], [5, 6, 7, 8]], jnp.int32)
        positions = jnp.asarray([13, 21], jnp.int32)
        active = jnp.asarray([True, True])
        key = jax.random.PRNGKey(11)
        if write == "span":
            vals = jax.random.normal(key, (1, 6, 2, 16))
            fn = lambda pool: A._paged_write_span(pool, tables[:1], 5, vals)
        elif write == "token":
            vals = jax.random.normal(key, (2, 2, 16))
            fn = lambda pool: A._paged_write_token(pool, tables, positions,
                                                   active, vals)
        else:
            vals = jax.random.normal(key, (2, 3, 2, 16))
            fn = lambda pool: A._paged_write_multi(
                pool, tables, positions, active,
                jnp.asarray([3, 2], jnp.int32), vals)
        got, want = fn(flat), fn(four)
        assert got.shape == flat.shape
        assert not np.array_equal(np.asarray(got), np.asarray(flat))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want).reshape(flat.shape))


# ---------------------------------------------------------------------------
# transformer-level parity: full models through cfg.paged_attn_kernel
# ---------------------------------------------------------------------------

PARITY_ARCHS = ("qwen1.5-0.5b", "gemma3-12b", "deepseek-v2-lite-16b")


class TestTransformerLevelParity:
    @pytest.mark.parametrize("arch", PARITY_ARCHS)
    def test_chunked_prefill_and_decode_logits(self, arch):
        """prefill_chunk + decode_step_paged produce the same logits whether
        the paged read gathers pools ("ref") or streams KV blocks through
        the Pallas kernel ("interpret") — across the three attention
        families (GQA+bias, local:global window, MLA+MoE)."""
        cfg = registry.get_config(arch, smoke=True)
        params = tf.init_params(cfg, jax.random.PRNGKey(0))
        bs, chunk, mb = 8, 8, 4
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, cfg.vocab_size, size=13)
        table_row = jnp.arange(1, mb + 1, dtype=jnp.int32)[None]

        def drive(mode):
            c = cfg.with_(paged_attn_kernel=mode)
            specs = tf.paged_cache_specs(c, num_blocks=mb + 1, block_size=bs)
            caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
            outs = []
            for c0 in range(0, 16, chunk):
                ctoks = np.zeros(chunk, np.int32)
                real = prompt[c0: min(len(prompt), c0 + chunk)]
                ctoks[: len(real)] = real
                last = len(prompt) - 1 - c0 if c0 + chunk >= 16 else 0
                logits, caches = tf.prefill_chunk(
                    params, c, jnp.asarray(ctoks[None]), caches, table_row,
                    c0, last)
            outs.append(np.asarray(logits, np.float32))
            tok, pos = int(prompt[-1]), len(prompt)
            for _ in range(2):
                logits, caches = tf.decode_step_paged(
                    params, c, jnp.asarray([[tok]], jnp.int32), caches,
                    table_row, jnp.asarray([pos], jnp.int32),
                    jnp.asarray([True]))
                outs.append(np.asarray(logits, np.float32))
                tok = int(np.argmax(outs[-1][0, -1]))
                pos += 1
            return outs

        ref, ker = drive("ref"), drive("interpret")
        for a, b in zip(ref, ker):
            np.testing.assert_allclose(b, a, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

class TestPlanPagedAttn:
    def test_ring_grows_with_dma_pressure(self):
        """DMA-bound blocks (few query rows) want a deeper ring."""
        small_q = plan_paged_attn(block_bytes=1 << 20, compute_flops=1e6)
        big_q = plan_paged_attn(block_bytes=1 << 20, compute_flops=1e12)
        assert small_q.num_bufs > big_q.num_bufs >= 2
        assert small_q.chunks == small_q.num_bufs - 1

    def test_vmem_budget_shrinks_ring(self):
        p = plan_paged_attn(block_bytes=1 << 20, compute_flops=1e6,
                            vmem_budget=3 << 20)
        assert p.num_bufs <= 3
        assert p.vmem_bytes <= 3 << 20

    def test_budget_too_small_raises(self):
        with pytest.raises(ValueError):
            plan_paged_attn(block_bytes=4 << 20, compute_flops=1e6,
                            vmem_budget=1 << 20)

    def test_pinned_ring_honored(self):
        assert plan_paged_attn(block_bytes=1 << 20, compute_flops=1e6,
                               num_bufs=2).num_bufs == 2

    def test_timing_cache_feeds_ring_depth(self):
        """A measured fast-link/slow-compute host flips the plan toward a
        shallow ring; the ambient default cache is honored too."""
        fast_link = TimingCache()
        fast_link.record(block_bytes=1e6, compute_flops=1e9,
                         t_dma=1e-5, t_compute=1e-2)
        deep = plan_paged_attn(block_bytes=1 << 20, compute_flops=1e6)
        shallow = plan_paged_attn(block_bytes=1 << 20, compute_flops=1e6,
                                  timing=fast_link)
        assert shallow.num_bufs <= deep.num_bufs
        set_default_timing_cache(fast_link)
        try:
            ambient = plan_paged_attn(block_bytes=1 << 20, compute_flops=1e6)
            assert ambient.num_bufs == shallow.num_bufs
        finally:
            set_default_timing_cache(None)
