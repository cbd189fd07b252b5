"""Pallas paged-attention: stream KV blocks through a VMEM ring.

The serving engine's paged read path used to gather every lane's logical KV
sequence out of the shared block pools — `pool[tables]` materializes a
(B, MB*block_size, ...) array in HBM per layer per step, pure un-overlapped
traffic that grows with the table width whether or not the context is live.
This kernel is the generalized ping-pong schedule applied to attention
itself:

  PIM macro           ->  one physical KV block resident in a VMEM ring slot
  weight rewrite      ->  async HBM->VMEM DMA of the NEXT logical block(s)
  compute             ->  the per-block online-softmax flash step
  off-chip bandwidth  ->  HBM DMA bandwidth
  consecutive GeMMs   ->  the flattened (lane, logical-block) grid steps

Block tables and per-lane positions ride in as SCALAR-PREFETCH operands
(`pltpu.PrefetchScalarGridSpec`), so the kernel can compute each DMA's
source — `pool[tables[lane, j]]` — before the grid step that consumes it.
The DMA issue schedule is exactly `gpp_matmul`'s chunk-issue ring
(`_make_chunk_ops` / `_run_chunk_schedule`, factored out in PR 2): with a
ring of G buffers, block j's bytes arrive in C = G-1 chunks during the C
preceding grid steps, so DMA traffic stays flat at one block per flash step
and the compute never waits.  Because the schedule is phrased over global
steps (lane-major), the ring keeps streaming across lane boundaries — lane
b+1's first blocks are in flight while lane b's tail blocks compute.

The gathered (B, MB*bs, ...) sequence is never formed: ragged last blocks,
unmapped table entries (physical block 0, the reserved null block), inactive
lanes parked on block 0, and sliding-window expiry are all handled by the
per-block mask, not by a dense materialized mask.  Blocks wholly outside a
lane's visible range — past its position, or expired behind the sliding
window — are skipped entirely: both the DMA (start AND wait sites evaluate
the same pure predicate over the prefetched scalars, so the semaphore
pairing holds) and the flash update, so per-step HBM traffic is the lane's
LIVE blocks, not the table width.  Unmapped-but-visible entries (only an
inactive lane parked at position 0) read the null block and are masked.

One kernel body serves the whole family:

  GQA / MHA / sliding window   pool_a = k  (nb, bs, KVH*hd)
                               pool_b = v  (nb, bs, KVH*hd)
  MLA (weight-absorbed MQA)    pool_a = c_kv   (nb, bs, kv_lora)
                               pool_b = k_rope (nb, bs, rope_dim)
      with q absorbed through w_uk (models/attention._mla_absorbed_q):
      logits = q_abs . concat(c_kv, k_rope), values = c_kv, and the
      latent output is up-projected through w_uv after the kernel —
      exact same math as the gather path, reassociated.

Queries arrive pre-scaled and pre-transposed as (B, KVH, rep*S, dk) so the
kernel body is nothing but DMA waits, two batched dot_generals (batch =
KV head, leading), and the online-softmax update — no in-kernel transposes
or reshapes: a (bs, KVH*hd) KV tile splits into heads by static lane
slices.  The model's GQA pools are stored in that lane-dense layout
(`models.attention.paged_cache_specs`): stored as (nb, bs, KVH, hd), a hd
under 128 lanes (or KVH x hd short of a whole tile) made XLA keep the pool
block-minor and relay all of it out around every write and every call.
(nb, bs, KVH, hd) pools are still accepted and flattened here.

Decode is S=1 with per-lane positions; a prefill chunk is B=1,
S=chunk with ANY start position (prefix-cache hits resume prefill
mid-block — the visibility predicate and per-(row, slot) mask are
position-exact, never block-aligned-assuming); both compile to the same
kernel.

Shared-prefix aliasing contract: with the prefix cache on, one physical
block may appear in SEVERAL lanes' tables (refcounted shares of a common
prompt prefix).  That is safe here by construction — this kernel only ever
READS the pools (`pl.BlockSpec(memory_space=pl.ANY)` inputs, DMA'd into the
VMEM ring; the only output is the attention result).  All pool writes live
in `models.attention._paged_write_span` / `_paged_write_token`, and the
engine asserts before every write that the target blocks are exclusively
owned (`PagedKVCache.assert_writable`): shared blocks are read-only until
`fork_block` copies them out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import plan_paged_attn
from repro.kernels.gpp_matmul import (_make_chunk_ops, _run_chunk_schedule,
                                      ring_chunks, sublane_tile, target_chip)

NEG_INF = float("-inf")


def _split_heads(tile, kvh: int):
    """(bs, KVH*hd) -> (KVH, bs, hd) by static lane slices stacked on a new
    leading axis: Mosaic has no lane-splitting reshape, and the batched
    dot_generals want their batch (head) dimension leading."""
    hd = tile.shape[-1] // kvh
    return jnp.stack([tile[:, h * hd:(h + 1) * hd] for h in range(kvh)])


def _dot_nt(a, b):
    """a @ b^T: (r, d) x (t, d) -> (r, t), accumulated in f32."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bdot_nt(a, b):
    """Batched a @ b^T over a leading batch dim: (N, r, d) x (N, t, d) ->
    (N, r, t), accumulated in f32."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _paged_attn_kernel(tables_ref, pos_ref, q_ref, pa_hbm, pb_hbm, out_ref,
                       m_ref, l_ref, acc_ref, ring_a, ring_b, sem_a, sem_b,
                       *, grid_bj: tuple, S: int, kvh: int, rep: int, bs: int,
                       G: int, C: int, window: "int | None", mla: bool,
                       out_dtype):
    """Kernel body; grid = (B, MB), logical-block dim innermost."""
    B, MB = grid_bj
    b, j = pl.program_id(0), pl.program_id(1)
    s = b * MB + j                       # global step, lane-major
    S_total = B * MB

    def live(step):
        """True iff the logical block consumed at `step` overlaps its lane's
        visible key range (pos - window, pos + S - 1].  Pure in the
        prefetched scalars, so the DMA start sites (earlier grid steps) and
        wait sites agree and the semaphore pairing holds; dead blocks cost
        neither DMA nor flash compute."""
        lane = step // MB
        lj = jax.lax.rem(step, MB)
        p = pos_ref[lane]
        ok = lj * bs <= p + (S - 1)
        if window is not None:
            ok &= (lj + 1) * bs - 1 > p - window
        return ok

    def tile_slice(pool):
        def slice_fn(step, lo: int, hi: int):
            lane = step // MB
            phys = tables_ref[lane, jax.lax.rem(step, MB)]
            return pool.at[phys, pl.ds(lo, hi - lo), :]
        return slice_fn

    start_a, wait_a = _make_chunk_ops(ring_a, sem_a, G, C, bs,
                                      tile_slice(pa_hbm))
    start_b, wait_b = _make_chunk_ops(ring_b, sem_b, G, C, bs,
                                      tile_slice(pb_hbm))

    def start_chunk(step, c):
        @pl.when(live(step))
        def _():
            start_a(step, c)
            start_b(step, c)

    def wait_chunk(step, c):
        @pl.when(live(step))
        def _():
            wait_a(step, c)
            wait_b(step, c)

    _run_chunk_schedule(s, S_total, G, C, start_chunk, wait_chunk)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live(s))
    def _flash_step():
        slot = jax.lax.rem(s, G)
        ka = ring_a[slot]                # (bs, Fa)
        kb = ring_b[slot]                # (bs, Fb)
        rS = rep * S
        if mla:
            # weight-absorbed MLA is MQA: the key is concat(latent, rope)
            # along lanes, so its logits are the sum of the two lane blocks'
            # — no in-kernel concatenate; values are the latent (no second
            # value ring needed).
            fa = ka.shape[-1]
            q2 = q_ref[0, 0]                                 # (rep*S, dk)
            logits = (_dot_nt(q2[:, :fa], ka)
                      + _dot_nt(q2[:, fa:], kb)).reshape(1, rS, bs)
            v3 = ka.reshape(1, bs, fa)                       # (1, bs, dv)
        else:
            k3 = _split_heads(ka, kvh)   # (KVH, bs, hd)
            v3 = _split_heads(kb, kvh)
            logits = _bdot_nt(q_ref[0], k3)  # (KVH, rep*S, bs), f32;
            #                                  q pre-scaled (KVH, rep*S, dk)

        # mask per (query-row, key-slot): flattened row i is (r, s_) with
        # s_ = i % S, so qpos = pos[lane] + s_; key slot t holds absolute
        # position j*bs + t.  Ragged tails, null-block reads, and window
        # expiry all fall out of this one predicate.
        srow = jax.lax.broadcasted_iota(jnp.int32, (rS, bs), 0) % S
        qpos = pos_ref[b] + srow
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rS, bs), 1)
        valid = kpos <= qpos
        if window is not None:
            valid &= kpos > qpos - window
        logits = jnp.where(valid[None], logits, NEG_INF)

        # online softmax (the _sdpa_kv_chunked recurrence, per KV block)
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        pv = jax.lax.dot_general(           # (KVH, rep*S, dv)
            p.astype(v3.dtype), v3,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc * corr[..., None] + pv

    @pl.when(j == MB - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        out_ref[0] = out.astype(out_dtype)


def paged_attention(
    q: jnp.ndarray,
    pool_a: jnp.ndarray,
    pool_b: jnp.ndarray,
    tables: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    num_kv_heads: int,
    scale: float,
    window: "int | None" = None,
    mla: bool = False,
    num_bufs: "int | None" = None,
    vmem_budget: "int | None" = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Block-table paged attention over shared KV pools.

    Args:
      q: (B, S, H, dk) queries.  Decode: S == 1 with per-lane positions;
         prefill chunk: B == 1 with any (not necessarily block-aligned)
         start position.
      pool_a / pool_b: shared physical pools, leading dims (nb, bs, ...).
         GQA: k / v of (nb, bs, KVH*hd), the model's lane-dense layout, or
         (nb, bs, KVH, hd), flattened to it here.  MLA: c_kv
         (nb, bs, kv_lora) / k_rope (nb, bs, rope_dim) with `mla=True` and
         q already absorbed through w_uk (dk = kv_lora + rope_dim); returns
         the latent output (B, S, H, kv_lora) for the caller to up-project
         through w_uv.
      tables: (B, MB) int32 block table — entry 0 is the reserved null block
         (unmapped / inactive lanes), masked by construction.
      positions: (B,) int32 — each lane's query start position (decode: the
         token's position; prefill: chunk start).
      num_kv_heads: KVH for the GQA pools (ignored under `mla`).
      scale: softmax scale, folded into q before the kernel.
      window: sliding-window size; expiry is masked per block.
      num_bufs: KV-block ring depth G; None plans it via
         `core.schedule.plan_paged_attn` (VMEM budget + TimingCache rates).
      vmem_budget: scoped-VMEM limit the kernel is compiled with and the
         ring is planned against (default: the target chip's limit).
      interpret: run in interpret mode (CPU validation).

    Returns: (B, S, H, dv) attention output in q.dtype (dv = hd, or kv_lora
    under `mla`).
    """
    B, S, H, dk = q.shape
    Bt, MB = tables.shape
    if Bt != B or positions.shape != (B,):
        raise ValueError(
            f"tables {tables.shape} / positions {positions.shape} do not "
            f"match q batch {B}")
    if pool_a.shape[:2] != pool_b.shape[:2]:
        raise ValueError(
            f"pool block dims differ: {pool_a.shape} vs {pool_b.shape}")
    nb, bs = pool_a.shape[:2]
    kvh = 1 if mla else num_kv_heads
    if H % kvh:
        raise ValueError(f"{H} heads not divisible by {kvh} kv heads")
    rep = H // kvh
    # flatten trailing dims: one 3-D (nb, bs, F) layout per pool, so the ring
    # DMA helpers see the same (rows, lanes) tiles as the matmul kernel (a
    # no-op for the model's pools, which are stored this way).
    pa = pool_a.reshape(nb, bs, -1)
    pb = pool_b.reshape(nb, bs, -1)
    Fa, Fb = pa.shape[-1], pb.shape[-1]
    if mla:
        dv = Fa
        if dk != Fa + Fb:
            raise ValueError(
                f"mla q dk={dk} != kv_lora {Fa} + rope {Fb}")
    else:
        dv = Fb // kvh
        if Fa != kvh * dk:
            raise ValueError(
                f"k pool trailing {pool_a.shape[2:]} does not match "
                f"{kvh} kv heads x head_dim {dk}")

    kdtype = pool_a.dtype
    # pre-scale and pre-transpose q outside the kernel: (B, S, KVH, rep, dk)
    # -> (B, KVH, rep*S, dk), mirroring _sdpa's q-scaling dtype discipline.
    qr = (q.astype(jnp.float32) * scale).astype(kdtype)
    q2 = (qr.reshape(B, S, kvh, rep, dk)
            .transpose(0, 2, 3, 1, 4)
            .reshape(B, kvh, rep * S, dk))

    rS = rep * S
    itemsize = jnp.dtype(kdtype).itemsize
    fixed = (kvh * rS * (dk + dv) * itemsize      # queries + output block
             + kvh * rS * (dv + 2) * 4)           # f32 acc + m + l
    chip = target_chip(interpret)
    vmem_limit = vmem_budget or chip.vmem_limit_bytes
    plan = plan_paged_attn(
        block_bytes=bs * (Fa + Fb) * itemsize,
        compute_flops=2.0 * rS * bs * (dk + dv) * kvh,
        fixed_bytes=fixed,
        num_bufs=num_bufs,
        vmem_budget=vmem_limit,
        chip=chip,
    )
    G = min(plan.num_bufs, max(1, B * MB))
    C = ring_chunks(G, bs, sublane_tile(itemsize))

    kernel = functools.partial(
        _paged_attn_kernel, grid_bj=(B, MB), S=S, kvh=kvh, rep=rep, bs=bs,
        G=G, C=C, window=window, mla=mla, out_dtype=q.dtype,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # tables, positions
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, kvh, rS, dk), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # pool_a: stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),      # pool_b: stays in HBM
        ],
        out_specs=pl.BlockSpec((1, kvh, rS, dv), lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, rS), jnp.float32),     # running max
            pltpu.VMEM((kvh, rS), jnp.float32),     # running denominator
            pltpu.VMEM((kvh, rS, dv), jnp.float32),  # f32 accumulator
            pltpu.VMEM((G, bs, Fa), kdtype),        # k / c_kv block ring
            pltpu.VMEM((G, bs, Fb), kdtype),        # v / k_rope block ring
            pltpu.SemaphoreType.DMA((G,)),
            pltpu.SemaphoreType.DMA((G,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, rS, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,  # sequential grid
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(tables.astype(jnp.int32), positions.astype(jnp.int32), q2, pa, pb)
    return (out.reshape(B, kvh, rep, S, dv)
               .transpose(0, 3, 1, 2, 4)
               .reshape(B, S, H, dv))
