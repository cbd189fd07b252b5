"""Attention variants: GQA (opt. QKV bias / sliding window / local:global),
MLA (DeepSeek-style latent compression), and cross-attention (VLM).

Cache layouts:
  full window   k/v: (B, S_max, KVH, hd), positions filled [0, pos)
  sliding (SWA) k/v: (B, W, KVH, hd) ring buffer indexed pos % W
  MLA           c_kv: (B, S_max, kv_lora), k_rope: (B, S_max, rope_dim)
                — the compressed-latent cache is the memory win.

All attention math accumulates in f32.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.schedule import round_up
from repro.kernels.ops import dense, paged_attn, resolve_paged_attn_mode
from repro.models.layers import rmsnorm_specs, rope, sds, yarn_mscale

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_scaling: object = None        # configs.base.YarnScaling or None
    rope_interleave: bool = False      # DeepSeek-V2 lane pairing (layers.rope)
    window: int | None = None          # sliding-window size (None = full)
    # MLA
    kv_lora_rank: int | None = None
    q_lora_rank: int | None = None
    rope_head_dim: int = 64
    dtype: object = jnp.bfloat16
    # kernels.ops.dense routing for every projection (cfg.dense_kernel):
    # "auto" streams big weights through the GPP Pallas kernel on TPU and
    # falls back to the bit-identical jnp path elsewhere
    dense_mode: str = "auto"
    # kernels.ops.paged_attn routing for the paged serving read path
    # (cfg.paged_attn_kernel): "ref" keeps the gather+_sdpa math below,
    # "pallas"/"interpret" stream KV blocks through the VMEM-ring kernel,
    # "auto" picks pallas on TPU and ref elsewhere
    paged_mode: str = "auto"

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank is not None

    def rope(self, x, positions):
        return rope(x, positions, self.rope_theta, self.rope_scaling,
                    self.rope_interleave)

    @property
    def mla_scale(self) -> float:
        """MLA's softmax scale: 1/sqrt(nope + rope), times YaRN's
        mscale(factor, mscale_all_dim)^2 where the config scales rope
        (DeepSeek-V2: 1.5896 at factor 40, mscale_all_dim 0.707) — the same
        in the reference, paged-reference and absorbed kernel forms."""
        scale = 1.0 / math.sqrt(self.head_dim + self.rope_head_dim)
        s = self.rope_scaling
        if s is not None and s.mscale_all_dim:
            m = yarn_mscale(s.factor, s.mscale_all_dim)
            scale *= m * m
        return scale


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_specs(c: AttnConfig):
    if c.is_mla:
        nope = c.head_dim
        sp = {
            "w_dkv": sds((c.d_model, c.kv_lora_rank + c.rope_head_dim), c.dtype),
            "w_uk": sds((c.kv_lora_rank, c.num_heads, nope), c.dtype),
            "w_uv": sds((c.kv_lora_rank, c.num_heads, nope), c.dtype),
            "w_o": sds((c.num_heads, nope, c.d_model), c.dtype),
            # the latent's RMSNorm, laid out as every norm ({"scale"}) so
            # that initializers treat it as one
            "kv_norm": rmsnorm_specs(c.kv_lora_rank, c.dtype),
        }
        if c.q_lora_rank:
            sp["w_dq"] = sds((c.d_model, c.q_lora_rank), c.dtype)
            sp["w_uq"] = sds((c.q_lora_rank, c.num_heads, nope + c.rope_head_dim), c.dtype)
            sp["q_norm"] = rmsnorm_specs(c.q_lora_rank, c.dtype)
        else:
            sp["w_q"] = sds((c.d_model, c.num_heads, nope + c.rope_head_dim), c.dtype)
        return sp
    sp = {
        "w_q": sds((c.d_model, c.num_heads, c.head_dim), c.dtype),
        "w_k": sds((c.d_model, c.num_kv_heads, c.head_dim), c.dtype),
        "w_v": sds((c.d_model, c.num_kv_heads, c.head_dim), c.dtype),
        "w_o": sds((c.num_heads, c.head_dim, c.d_model), c.dtype),
    }
    if c.qkv_bias:
        sp["b_q"] = sds((c.num_heads, c.head_dim), c.dtype)
        sp["b_k"] = sds((c.num_kv_heads, c.head_dim), c.dtype)
        sp["b_v"] = sds((c.num_kv_heads, c.head_dim), c.dtype)
    return sp


def cross_attn_specs(c: AttnConfig):
    """Cross-attention (queries from text, K/V from encoder embeddings)."""
    return {
        "w_q": sds((c.d_model, c.num_heads, c.head_dim), c.dtype),
        "w_k": sds((c.d_model, c.num_kv_heads, c.head_dim), c.dtype),
        "w_v": sds((c.d_model, c.num_kv_heads, c.head_dim), c.dtype),
        "w_o": sds((c.num_heads, c.head_dim, c.d_model), c.dtype),
        "q_norm": sds((c.head_dim,), c.dtype),
        "k_norm": sds((c.head_dim,), c.dtype),
    }


def cache_specs(c: AttnConfig, batch: int, max_len: int):
    """KV-cache ShapeDtypeStructs for decode."""
    if c.is_mla:
        return {
            "c_kv": sds((batch, max_len, c.kv_lora_rank), c.dtype),
            "k_rope": sds((batch, max_len, c.rope_head_dim), c.dtype),
        }
    span = min(max_len, c.window) if c.window else max_len
    return {
        "k": sds((batch, span, c.num_kv_heads, c.head_dim), c.dtype),
        "v": sds((batch, span, c.num_kv_heads, c.head_dim), c.dtype),
    }


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, mask, scale):
    """q: (B,S,H,hd) k/v: (B,T,KVH,hd) mask: (B,S,T) or (S,T) broadcastable.

    k/v stay in their storage dtype (bf16) with f32 ACCUMULATION via
    preferred_element_type — an .astype(f32) on a 32k-token KV cache would
    materialize (and reshard) a full-size f32 copy.  probs are cast back to
    the storage dtype for the PV matmul (standard flash-kernel practice)."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    qr = (q.astype(jnp.float32) * scale).astype(k.dtype)
    qr = qr.reshape(B, S, KVH, rep, hd)
    logits = jnp.einsum("bsgrh,btgh->bgrst", qr, k,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(mask[:, None, None] if mask.ndim == 3 else mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,btgh->bsgrh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    # v's head dim may differ from q/k's (MLA: values are nope-only)
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def causal_mask(S: int, T: int, q_offset: int = 0, window: int | None = None):
    """(S, T) mask: query i (global pos q_offset+i) sees keys j <= pos, and
    within `window` if set.  q_offset may be traced (chunked attention)."""
    qpos = q_offset + jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


def ambient_constraint(x, spec):
    """with_sharding_constraint against the ambient mesh, if one is set and
    covers the named axes (no-op on mesh-less CPU test runs).  A constraint
    that fails raises."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not all(a is None or a in mesh.axis_names for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _tp_size() -> int:
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else mesh.shape.get("model", 1)


ATTN_Q_CHUNK = 512  # query-block size for memory-efficient attention
ATTN_KV_CHUNK = 1024  # key-block size for context-parallel attention


def _sdpa_chunked(q, k, v, scale, *, window=None, chunk=ATTN_Q_CHUNK):
    """Causal attention with query blocking: scores for one (chunk x T) block
    live at a time and are rematerialized in backward — peak memory
    B*H*chunk*T*4 bytes instead of B*H*S*T*4 (the 17 GiB -> 2 GiB difference
    at seq 4k/32k).  TPU-adaptation note (DESIGN.md): this is the pure-XLA
    stand-in for a flash-attention kernel; the blocks are VMEM-sized."""
    B, S, H, hd = q.shape
    if S <= chunk or S % chunk:
        return _sdpa(q, k, v, causal_mask(S, k.shape[1], 0, window), scale)
    nq = S // chunk
    qs = q.reshape(B, nq, chunk, H, hd).swapaxes(0, 1)  # (nq, B, qc, H, hd)

    @jax.checkpoint
    def body(carry, inp):
        qc, i = inp
        mask = causal_mask(chunk, k.shape[1], i * chunk, window)
        return carry, _sdpa(qc, k, v, mask, scale)

    _, outs = jax.lax.scan(body, jnp.zeros((), jnp.int32),
                           (qs, jnp.arange(nq)))
    # out head dim follows v (MLA values are nope-only, narrower than q)
    return outs.swapaxes(0, 1).reshape(B, S, H, v.shape[-1])


def _sdpa_kv_chunked(q, k, v, scale, *, window=None, chunk=ATTN_KV_CHUNK,
                     q_offset=0, varying_axes=None):
    """Online-softmax attention scanning KEY blocks.  q rows may be a
    sequence-shard (context parallelism): `q_offset` gives their global
    position for the causal mask.  Peak memory is one (S_local x chunk)
    block of logits."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    KVH = k.shape[2]
    rep = H // KVH
    vd = v.shape[-1]
    if T % chunk or T <= chunk:
        return _sdpa(q, k, v, causal_mask(S, T, q_offset, window), scale)
    nt = T // chunk
    qr = (q.astype(jnp.float32) * scale).astype(k.dtype).reshape(B, S, KVH, rep, hd)
    ks = k.reshape(B, nt, chunk, KVH, hd).swapaxes(0, 1)
    vs = v.reshape(B, nt, chunk, KVH, vd).swapaxes(0, 1)

    m0 = jnp.full((B, KVH, rep, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KVH, rep, S), jnp.float32)
    a0 = jnp.zeros((B, S, KVH, rep, vd), jnp.float32)
    if varying_axes:
        # under shard_map the carry must match the body's varying-axis type
        m0, l0, a0 = (jax.lax.pcast(t, tuple(varying_axes), to="varying")
                      for t in (m0, l0, a0))

    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry
        kc, vc, j = inp
        logits = jnp.einsum("bsgrh,btgh->bgrst", qr, kc,
                            preferred_element_type=jnp.float32)
        # query i is global position q_offset+i; keys are at j*chunk + t
        mask = causal_mask(S, chunk, q_offset - j * chunk, window)
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard: fully-masked rows keep m=-inf; use a safe max for exps
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr.transpose(0, 3, 1, 2)[..., None]
                   + jnp.einsum("bgrst,btgh->bsgrh", p.astype(vc.dtype), vc,
                                preferred_element_type=jnp.float32))
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (ks, vs, jnp.arange(nt)))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, S, H, vd).astype(q.dtype)


def _attend(q, k, v, scale, *, window=None):
    """Dispatch: context-parallel attention (shard_map: q sequence-sharded
    over the model axis, k/v replicated per data shard, masks on global
    positions) when heads don't divide the TP axis; q-chunked otherwise.

    shard_map (not sharding constraints) because the SPMD partitioner is
    free to re-shard einsum internals mid-graph — on a 28-head model it
    chooses head_dim contraction sharding and all-reduces every logits
    block (~7 GiB x layers x chunks).  Manual mapping pins the layout."""
    H = q.shape[2]
    tp = _tp_size()
    if tp > 1 and H % tp and q.shape[1] % tp == 0 and q.shape[1] > 1:
        from jax.sharding import PartitionSpec as P
        mesh = jax.sharding.get_abstract_mesh()
        dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        B = q.shape[0]
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        bspec = dp if (dp and B % dp_size == 0) else None
        S_local = q.shape[1] // tp

        def local(qb, kb, vb):
            idx = jax.lax.axis_index("model")
            return _sdpa_kv_chunked(qb, kb, vb, scale, window=window,
                                    q_offset=idx * S_local,
                                    varying_axes=mesh.axis_names)

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(bspec, "model", None, None),
                      P(bspec, None, None, None),
                      P(bspec, None, None, None)),
            out_specs=P(bspec, "model", None, None),
        )(q, k, v)
    return _sdpa_chunked(q, k, v, scale, window=window)


# ---------------------------------------------------------------------------
# GQA (covers MHA, SWA, local/global via `window`)
# ---------------------------------------------------------------------------

def gqa_project_qkv(p, c: AttnConfig, x, positions):
    """q/k/v projections through `kernels.ops.dense` (bias fused into the
    streaming epilogue); "ref" routing reproduces the einsum math exactly."""
    bq = p["b_q"] if c.qkv_bias else None
    bk = p["b_k"] if c.qkv_bias else None
    bv = p["b_v"] if c.qkv_bias else None
    q = dense(x, p["w_q"], bias=bq, mode=c.dense_mode)
    k = dense(x, p["w_k"], bias=bk, mode=c.dense_mode)
    v = dense(x, p["w_v"], bias=bv, mode=c.dense_mode)
    q = c.rope(q, positions)
    k = c.rope(k, positions)
    return q, k, v


def gqa_forward(p, c: AttnConfig, x, positions):
    """Training/prefill self-attention (causal, optional window)."""
    B, S, _ = x.shape
    q, k, v = gqa_project_qkv(p, c, x, positions)
    out = _attend(q, k, v, 1.0 / math.sqrt(c.head_dim), window=c.window)
    return dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2)


def gqa_prefill(p, c: AttnConfig, x, positions, max_len: int):
    """Prefill: returns (out, cache) with cache laid out for decode."""
    B, S, _ = x.shape
    q, k, v = gqa_project_qkv(p, c, x, positions)
    out = _attend(q, k, v, 1.0 / math.sqrt(c.head_dim), window=c.window)
    span = min(max_len, c.window) if c.window else max_len
    kc = jnp.zeros((B, span, c.num_kv_heads, c.head_dim), k.dtype)
    vc = jnp.zeros_like(kc)
    if c.window and S > span:
        k_tail, v_tail = k[:, -span:], v[:, -span:]
        # ring layout: slot = pos % span
        slots = (positions[:, -span:]) % span
        kc = kc.at[jnp.arange(B)[:, None], slots].set(k_tail)
        vc = vc.at[jnp.arange(B)[:, None], slots].set(v_tail)
    else:
        kc = jax.lax.dynamic_update_slice(kc, k[:, : min(S, span)], (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v[:, : min(S, span)], (0, 0, 0, 0))
    return (dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2),
            {"k": kc, "v": vc})


def gqa_decode(p, c: AttnConfig, x, cache, pos):
    """One-token decode. x: (B, 1, D); pos: scalar current position."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = gqa_project_qkv(p, c, x, positions)
    span = cache["k"].shape[1]
    slot = pos % span if c.window else pos
    kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    kpos_abs = jnp.arange(span)
    if c.window:
        # ring: entry j holds absolute position p' with p' % span == j,
        # p' in (pos-span, pos]
        kpos_abs = pos - ((pos - kpos_abs) % span)
        valid = (kpos_abs >= 0) & (kpos_abs >= pos - (c.window - 1))
    else:
        valid = kpos_abs <= pos
    mask = valid[None, None, :]  # (1,1,span) -> broadcast (B,1,span)
    mask = jnp.broadcast_to(mask, (B, 1, span))
    out = _sdpa(q, kc, vc, mask, 1.0 / math.sqrt(c.head_dim))
    return (dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2),
            {"k": kc, "v": vc})


# ---------------------------------------------------------------------------
# paged KV (block-pool) read/write — serving subsystem
# ---------------------------------------------------------------------------
#
# Pool layouts (no per-lane batch dim; capacity shared across lanes):
#   full/window k/v: (num_blocks, block_size, KVH*hd)
#   MLA            : c_kv (num_blocks, block_size, kv_lora),
#                    k_rope (num_blocks, block_size, round_up(rope_dim, 128))
# Every pool is lane-dense: its minor dim is a token's whole row, the
# (bs, F) tile `paged_attention` streams.  A (..., KVH, hd) pool whose hd
# (or KVH x hd tile) does not fill the TPU's (sublane, 128-lane) tiling made
# XLA store it block-minor and relayout the whole pool several times per
# layer in every step program; the flat pool is written in place.  Writes
# flatten each token's (KVH, hd) row into the last dim.
# The k_rope pool is zero-padded to whole 128-lane tiles: Mosaic cannot DMA
# a block out of an HBM array whose lane dim is not tile-aligned.  Padded
# lanes are written as zeros and meet zero query lanes, so logits are exact.
# A lane's logical block b (absolute positions [b*bs, (b+1)*bs)) lives at
# physical block `tables[lane, b]`; block 0 is the reserved null/scratch
# block (unmapped reads land there and are masked, inactive writes are
# parked there).  Window layers use the same absolute-slot layout as full
# attention (no ring) — the window is enforced by the mask, so the existing
# `causal_mask` / `_sdpa` kernels carry the paged path unchanged.


def paged_cache_specs(c: AttnConfig, num_blocks: int, block_size: int):
    """Pool ShapeDtypeStructs for one attention layer (shared across lanes):
    GQA/MHA k and v are (num_blocks, block_size, KVH*hd), lane-dense (see
    the layout note above)."""
    if c.is_mla:
        return {
            "c_kv": sds((num_blocks, block_size, c.kv_lora_rank), c.dtype),
            "k_rope": sds((num_blocks, block_size,
                           round_up(c.rope_head_dim, 128)), c.dtype),
        }
    return {
        "k": sds((num_blocks, block_size, c.num_kv_heads * c.head_dim),
                 c.dtype),
        "v": sds((num_blocks, block_size, c.num_kv_heads * c.head_dim),
                 c.dtype),
    }


def _pad_lanes(x, width: int):
    """Zero-pad the last dim of `x` to `width` (the k_rope pool's lanes)."""
    pad = width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _paged_gather(pool, tables):
    """Gather a pool through block tables: (nb, bs, ...) x (B, MB) ->
    (B, MB*bs, ...) — each lane's logical KV sequence, position-ordered."""
    g = pool[tables]                                   # (B, MB, bs, ...)
    return g.reshape(tables.shape[0], -1, *pool.shape[2:])


def _paged_write_span(pool, table_row, start_pos, vals):
    """Write `vals` (1, S, ...) at absolute positions [start_pos, start_pos+S)
    of the lane whose table row is `table_row` (1, MB) — one (block, offset)
    scatter per token, so start_pos may be ANY token index.  Non-alignment
    arises from prefix-cache hits: prefill resumes at the matched token
    count, mid-block when a shared tail block was forked (the lane owns
    every block the span touches — shared blocks all sit below start_pos;
    the engine asserts this via `PagedKVCache.assert_writable`)."""
    bs = pool.shape[1]
    S = vals.shape[1]
    pos = start_pos + jnp.arange(S, dtype=jnp.int32)
    blk = jnp.take(table_row[0], pos // bs)
    return pool.at[blk, pos % bs].set(vals[0].reshape(S, *pool.shape[2:]))


def _paged_write_token(pool, tables, positions, active, vals):
    """Scatter one token per lane: vals (B, ...) at each lane's `positions`.
    Inactive lanes are parked on null block 0 (their table lookup may be
    stale), so one fixed-shape scatter serves any active subset."""
    bs = pool.shape[1]
    B = tables.shape[0]
    blk = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, 0)
    off = jnp.where(active, positions % bs, 0)
    return pool.at[blk, off].set(vals.reshape(B, *pool.shape[2:]))


def _paged_write_multi(pool, tables, positions, active, nvalid, vals):
    """Scatter S tokens per lane: vals (B, S, ...) land at absolute
    positions `positions[b] + s` for s < nvalid[b] (speculative verify
    bursts).  Rows past a lane's real token count — and whole inactive
    lanes — are parked on null block 0, so ONE fixed (B, S) scatter shape
    serves every draft-length / acceptance pattern."""
    bs = pool.shape[1]
    S = vals.shape[1]
    pos = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = active[:, None] & (jnp.arange(S)[None, :] < nvalid[:, None])
    blk = jnp.take_along_axis(tables, jnp.where(valid, pos // bs, 0), axis=1)
    blk = jnp.where(valid, blk, 0)
    off = jnp.where(valid, pos % bs, 0)
    return pool.at[blk, off].set(vals.reshape(*pos.shape, *pool.shape[2:]))


def paged_mask(positions, T: int, *, S: int = 1, window: "int | None" = None):
    """(B, S, T) decode/verify mask over a gathered pool: key slot j holds
    absolute position j; query row s of lane b sits at positions[b] + s —
    valid iff j <= that (and within `window`).  S=1 is plain decode."""
    kpos = jnp.arange(T)[None, None, :]
    qpos = positions[:, None, None] + jnp.arange(S)[None, :, None]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


def _gqa_paged_attend(c: AttnConfig, q, kc, vc, tables, positions):
    """Dispatch the paged GQA read through `kernels.ops.paged_attn`: "ref"
    gathers each lane's logical sequence through the tables and runs the
    exact `_sdpa` math (`kernels.ref.paged_attn_ref`, the pre-kernel path
    bit-for-bit); the kernel modes stream KV blocks through the VMEM ring
    instead — the gathered (B, MB*bs, ...) sequence is never formed."""
    return paged_attn(q, kc, vc, tables, positions,
                      num_kv_heads=c.num_kv_heads,
                      scale=1.0 / math.sqrt(c.head_dim),
                      window=c.window, mode=c.paged_mode)


def gqa_prefill_paged(p, c: AttnConfig, x, cache, table_row, start_pos):
    """One prefill chunk (B=1): project, write whole blocks, attend over the
    lane's blocks.  x: (1, S, D), start_pos: traced block-aligned scalar."""
    S = x.shape[1]
    positions = start_pos + jnp.arange(S, dtype=jnp.int32)[None]
    q, k, v = gqa_project_qkv(p, c, x, positions)
    kc = _paged_write_span(cache["k"], table_row, start_pos, k)
    vc = _paged_write_span(cache["v"], table_row, start_pos, v)
    out = _gqa_paged_attend(c, q, kc, vc, table_row,
                            jnp.reshape(start_pos, (1,)).astype(jnp.int32))
    return (dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2),
            {"k": kc, "v": vc})


def gqa_decode_paged(p, c: AttnConfig, x, cache, tables, positions, active):
    """One-token decode across lanes at heterogeneous positions.
    x: (B, 1, D); tables: (B, MB); positions: (B,); active: (B,) bool."""
    q, k, v = gqa_project_qkv(p, c, x, positions[:, None])
    kc = _paged_write_token(cache["k"], tables, positions, active, k[:, 0])
    vc = _paged_write_token(cache["v"], tables, positions, active, v[:, 0])
    out = _gqa_paged_attend(c, q, kc, vc, tables, positions)
    return (dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2),
            {"k": kc, "v": vc})


def gqa_verify_paged(p, c: AttnConfig, x, cache, tables, positions, active,
                     nvalid):
    """Speculative verify: S = draft_len+1 tokens per lane in ONE forward
    pass, so the streamed weight working set amortizes over up to S tokens
    per lane instead of 1 (the GPP low-utilization fix for decode).
    x: (B, S, D); positions: (B,) per-lane START positions; nvalid: (B,)
    real tokens per lane — rows past it write null block 0 and their
    logits are ignored by the engine.  The paged-attention read path is
    position-exact for S > 1 already (query row s sits at positions[b]+s),
    so verify rides the same kernel as decode."""
    S = x.shape[1]
    pos2 = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = gqa_project_qkv(p, c, x, pos2)
    kc = _paged_write_multi(cache["k"], tables, positions, active, nvalid, k)
    vc = _paged_write_multi(cache["v"], tables, positions, active, nvalid, v)
    out = _gqa_paged_attend(c, q, kc, vc, tables, positions)
    return (dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2),
            {"k": kc, "v": vc})


def _mla_paged_attend(p, c: AttnConfig, q, ckv, kr, tables, positions,
                      *, prefill: bool):
    """Dispatch the paged MLA read.  "ref" gathers the latent pools and runs
    the unmodified `_mla_attend` (up-project k/v, then `_sdpa`).  The kernel
    modes use the weight-absorbed decode form instead: q is folded through
    w_uk so logits contract directly against the streamed c_kv/k_rope blocks
    (MQA over the latent), and the latent-space output is up-projected
    through w_uv after the kernel — the same math reassociated, with the
    compressed latent (not full K/V) the only thing that crosses HBM."""
    mode = resolve_paged_attn_mode(c.paged_mode, q, ckv, kr)
    if mode == "ref":
        ckv_seq = _paged_gather(ckv, tables)
        kr_seq = _paged_gather(kr, tables)[..., :c.rope_head_dim]
        if prefill:
            mask = causal_mask(q.shape[1], ckv_seq.shape[1], positions[0])
        else:
            mask = paged_mask(positions, ckv_seq.shape[1], S=q.shape[1])
        return _mla_attend(p, c, q, ckv_seq, kr_seq, mask)
    nope = c.head_dim
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    # absorb w_uk into q: q_abs[h] . c_kv[t] == q_nope[h] . k_nope[t, h]
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, p["w_uk"]).astype(q.dtype)
    q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)
    out_lat = paged_attn(q_eff, ckv, kr, tables, positions,
                         num_kv_heads=1, mla=True, scale=c.mla_scale,
                         mode=mode)
    out = jnp.einsum("bshr,rhn->bshn", out_lat, p["w_uv"]).astype(q.dtype)
    return dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2)


def mla_prefill_paged(p, c: AttnConfig, x, cache, table_row, start_pos):
    """MLA prefill chunk: the compressed latent (not full K/V) is what pages
    through the pool — the paper's capacity argument compounded."""
    S = x.shape[1]
    positions = start_pos + jnp.arange(S, dtype=jnp.int32)[None]
    q = _mla_q(p, c, x, positions)
    c_kv, k_rope = _mla_latent(p, c, x, positions)
    ckv = _paged_write_span(cache["c_kv"], table_row, start_pos, c_kv)
    kr = _paged_write_span(cache["k_rope"], table_row, start_pos,
                           _pad_lanes(k_rope, cache["k_rope"].shape[-1]))
    out = _mla_paged_attend(p, c, q, ckv, kr, table_row,
                            jnp.reshape(start_pos, (1,)).astype(jnp.int32),
                            prefill=True)
    return out, {"c_kv": ckv, "k_rope": kr}


def mla_decode_paged(p, c: AttnConfig, x, cache, tables, positions, active):
    q = _mla_q(p, c, x, positions[:, None])
    c_kv_new, k_rope_new = _mla_latent(p, c, x, positions[:, None])
    ckv = _paged_write_token(cache["c_kv"], tables, positions, active,
                             c_kv_new[:, 0])
    kr = _paged_write_token(cache["k_rope"], tables, positions, active,
                            _pad_lanes(k_rope_new[:, 0],
                                       cache["k_rope"].shape[-1]))
    out = _mla_paged_attend(p, c, q, ckv, kr, tables, positions,
                            prefill=False)
    return out, {"c_kv": ckv, "k_rope": kr}


def mla_verify_paged(p, c: AttnConfig, x, cache, tables, positions, active,
                     nvalid):
    """Speculative verify over the compressed-latent pools — see
    `gqa_verify_paged` for the contract; the per-row position vector
    (positions[b] + s) drives both rope and the paged mask."""
    S = x.shape[1]
    pos2 = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q = _mla_q(p, c, x, pos2)
    c_kv_new, k_rope_new = _mla_latent(p, c, x, pos2)
    ckv = _paged_write_multi(cache["c_kv"], tables, positions, active,
                             nvalid, c_kv_new)
    kr = _paged_write_multi(cache["k_rope"], tables, positions, active,
                            nvalid, _pad_lanes(k_rope_new,
                                               cache["k_rope"].shape[-1]))
    out = _mla_paged_attend(p, c, q, ckv, kr, tables, positions,
                            prefill=False)
    return out, {"c_kv": ckv, "k_rope": kr}


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

def _mla_q(p, c: AttnConfig, x, positions):
    from repro.models.layers import rmsnorm
    nope = c.head_dim
    if c.q_lora_rank:
        cq = rmsnorm(p["q_norm"], dense(x, p["w_dq"], mode=c.dense_mode))
        q = dense(cq, p["w_uq"], mode=c.dense_mode)
    else:
        q = dense(x, p["w_q"], mode=c.dense_mode)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = c.rope(q_rope, positions)
    return jnp.concatenate([q_nope, q_rope], axis=-1)


def _mla_latent(p, c: AttnConfig, x, positions):
    from repro.models.layers import rmsnorm
    d = dense(x, p["w_dkv"], mode=c.dense_mode)
    c_kv, k_rope = d[..., : c.kv_lora_rank], d[..., c.kv_lora_rank:]
    c_kv = rmsnorm(p["kv_norm"], c_kv)
    k_rope = c.rope(k_rope[..., None, :], positions)[..., 0, :]
    return c_kv, k_rope


def _mla_attend(p, c: AttnConfig, q, c_kv, k_rope, mask):
    nope = c.head_dim
    k_nope = dense(c_kv, p["w_uk"], mode=c.dense_mode)
    v = dense(c_kv, p["w_uv"], mode=c.dense_mode)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], c.rope_head_dim))], axis=-1
    )
    out = _sdpa(q, k, v, mask, c.mla_scale)
    out = out[..., :nope]  # v has nope dims; _sdpa padded? no: v dims = nope
    return dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2)


def mla_forward(p, c: AttnConfig, x, positions):
    B, S, _ = x.shape
    q = _mla_q(p, c, x, positions)
    c_kv, k_rope = _mla_latent(p, c, x, positions)
    k_nope = dense(c_kv, p["w_uk"], mode=c.dense_mode)
    v = dense(c_kv, p["w_uv"], mode=c.dense_mode)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], c.rope_head_dim))], axis=-1)
    out = _sdpa_chunked(q, k, v, c.mla_scale)
    return dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2)


def mla_prefill(p, c: AttnConfig, x, positions, max_len: int):
    B, S, _ = x.shape
    out = mla_forward(p, c, x, positions)
    c_kv, k_rope = _mla_latent(p, c, x, positions)
    ckv_buf = jnp.zeros((B, max_len, c.kv_lora_rank), c_kv.dtype)
    kr_buf = jnp.zeros((B, max_len, c.rope_head_dim), k_rope.dtype)
    ckv_buf = jax.lax.dynamic_update_slice(ckv_buf, c_kv[:, :max_len], (0, 0, 0))
    kr_buf = jax.lax.dynamic_update_slice(kr_buf, k_rope[:, :max_len], (0, 0, 0))
    return out, {"c_kv": ckv_buf, "k_rope": kr_buf}


def mla_decode(p, c: AttnConfig, x, cache, pos):
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = _mla_q(p, c, x, positions)
    c_kv_new, k_rope_new = _mla_latent(p, c, x, positions)
    ckv = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv_new, (0, pos, 0))
    kr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope_new, (0, pos, 0))
    T = ckv.shape[1]
    mask = jnp.broadcast_to((jnp.arange(T) <= pos)[None, None, :], (B, 1, T))
    out = _mla_attend(p, c, q, ckv, kr, mask)
    return out, {"c_kv": ckv, "k_rope": kr}


# ---------------------------------------------------------------------------
# cross-attention (text queries over encoder embeddings)
# ---------------------------------------------------------------------------

def cross_attn_forward(p, c: AttnConfig, x, enc):
    """x: (B, S, D) text; enc: (B, T, D) patch/frame embeddings (stubbed
    modality frontend).  No causal mask; no cache growth during decode."""
    from repro.models.layers import rmsnorm
    q = dense(x, p["w_q"], mode=c.dense_mode)
    k = dense(enc, p["w_k"], mode=c.dense_mode)
    v = dense(enc, p["w_v"], mode=c.dense_mode)
    q = rmsnorm({"scale": p["q_norm"]}, q)
    k = rmsnorm({"scale": p["k_norm"]}, k)
    B, S = x.shape[:2]
    T = enc.shape[1]
    mask = jnp.ones((B, S, T), bool)
    out = _sdpa(q, k, v, mask, 1.0 / math.sqrt(c.head_dim))
    return dense(out, p["w_o"], mode=c.dense_mode, contract_dims=2)
