"""Pure-jnp oracles for the Pallas kernels."""
import jax.numpy as jnp


def matmul_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """y[M,N] = x[M,K] @ w[K,N] accumulated in f32, cast back to x.dtype."""
    acc = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return acc.astype(x.dtype)


def dense_ref(x: jnp.ndarray, w: jnp.ndarray, *, bias=None, w_scale=None,
              activation: str | None = None) -> jnp.ndarray:
    """Oracle for gpp_matmul's fused epilogue: f32 accumulation, then
    per-column dequant scale, bias, activation — all in f32 — cast to x.dtype."""
    from repro.kernels.gpp_matmul import _ACTIVATIONS  # single source of truth
    acc = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    if w_scale is not None:
        acc = acc * jnp.asarray(w_scale, jnp.float32).reshape(1, -1)
    if bias is not None:
        acc = acc + jnp.asarray(bias, jnp.float32).reshape(1, -1)
    return _ACTIVATIONS[activation](acc).astype(x.dtype)


def dense_grouped_ref(x: jnp.ndarray, w: jnp.ndarray, *, bias=None,
                      w_scale=None, activation: str | None = None) -> jnp.ndarray:
    """Oracle for gpp_matmul_grouped's fused epilogue: per-expert
    y[e] = act(x[e] @ w[e] [* w_scale[e]] [+ bias[e]]), f32 accumulation
    with the dequant scale applied post-accumulation (int8 streaming),
    cast to x.dtype."""
    from repro.kernels.gpp_matmul import _ACTIVATIONS  # single source of truth
    E = x.shape[0]
    acc = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                     w.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    if w_scale is not None:
        sc = jnp.asarray(w_scale, jnp.float32)
        sc = sc if sc.ndim == 0 else sc.reshape(E, 1, -1)
        acc = acc * sc
    if bias is not None:
        acc = acc + jnp.asarray(bias, jnp.float32)[:, None, :]
    return _ACTIVATIONS[activation](acc).astype(x.dtype)


def paged_attn_ref(q, pool_a, pool_b, tables, positions, *, num_kv_heads,
                   scale, window=None, mla=False) -> jnp.ndarray:
    """Oracle for `kernels.paged_attention.paged_attention`: gather the pools
    through the block tables into each lane's (MB*bs, ...) logical sequence,
    then run the exact `models.attention._sdpa` math (same casts, same f32
    accumulation, same -1e30 masking) over a dense position mask.  This IS
    the pre-kernel serving read path — mode="ref" routes here so existing
    paged-engine numerics are unchanged when the kernel is off.
    """
    import jax

    B, S, H, dk = q.shape
    bs = pool_a.shape[1]

    def gather(pool):
        g = pool[tables]                          # (B, MB, bs, ...)
        return g.reshape(B, -1, *pool.shape[2:])

    if mla:
        kseq = jnp.concatenate([gather(pool_a), gather(pool_b)], axis=-1)
        kseq = kseq[:, :, None, :]                # MQA: one shared kv head
        vseq = gather(pool_a)[:, :, None, :]
        kvh = 1
    else:
        kvh = num_kv_heads
        # (B, T, KVH, hd) from either pool layout, (nb, bs, KVH*hd) or
        # (nb, bs, KVH, hd)
        kseq = gather(pool_a).reshape(B, -1, kvh, dk)
        vseq = gather(pool_b).reshape(*kseq.shape[:3], -1)
    T = kseq.shape[1]

    # dense mask over the gathered sequence: key slot t holds absolute
    # position t; query row s sits at positions[b] + s.
    qpos = positions[:, None, None] + jnp.arange(S)[None, :, None]
    kpos = jnp.arange(T)[None, None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window

    # _sdpa replica (models/attention.py) — keep the two in lockstep.
    rep = H // kvh
    qr = (q.astype(jnp.float32) * scale).astype(kseq.dtype)
    qr = qr.reshape(B, S, kvh, rep, dk)
    logits = jnp.einsum("bsgrh,btgh->bgrst", qr, kseq,
                        preferred_element_type=jnp.float32)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,btgh->bsgrh", probs.astype(vseq.dtype), vseq,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, vseq.shape[-1]).astype(q.dtype)


def streamed_gemm_seq_ref(x: jnp.ndarray, ws: jnp.ndarray) -> jnp.ndarray:
    """Reference for a *sequence* of GeMMs with streamed weights (the paper's
    consecutive-GeMM BLAS workload): ys[r] = x @ ws[r] for each round r."""
    return jnp.einsum(
        "mk,rkn->rmn",
        x.astype(jnp.float32),
        ws.astype(jnp.float32),
    ).astype(x.dtype)
