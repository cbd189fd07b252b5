"""Plain float32 reference of a DeepSeek-V2-architecture decoder
(DeepSeek-V2, DeepSeek-V2-Lite): the published forward pass in
`jax.numpy`, for one sequence at a time, with no kernel, no cache, no
batching and no expert capacity.  It imports nothing of the program.

Per layer, as in transformers' DeepSeek-V2 modelling code:
- RMSNorm (eps from the config), then latent attention without q
  compression: q = h Wq split into nope and rope parts; the latent
  [c, k_pe] = h Wdkv, c RMS-normed and up-projected to k_nope (w_uk) and v
  (w_uv); rope on q_pe and the one shared k_pe; causal softmax in float32
  over q.k scaled by (nope + rope)^-0.5 x mscale(factor, mscale_all_dim)^2
  when the config sets YaRN; output projection; residual.
- RoPE as `DeepseekV2YarnRotaryEmbedding` and `apply_rotary_pos_emb`: YaRN
  frequencies (theta^(-2j/d) blended with it over `factor` by a linear
  ramp between the correction dims of `beta_fast` and `beta_slow`), cos
  and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim);
  lanes de-interleaved ((2j, 2j+1) -> (j, j + d/2)) before rotate-half.
- RMSNorm, then the first `first_k_dense_replace` layers a SwiGLU MLP; the
  others the MoE: router logits in float32, softmax, each token's own top
  k experts, their weights renormalised only where `norm_topk_prob` says
  so (else times `routed_scaling_factor`), the sum of those experts'
  SwiGLU outputs, plus the shared experts' SwiGLU; residual.
- A final RMSNorm and the LM head (the embedding's transpose when tied).

Departures: none in the mathematics.  The routed sum is computed as every
expert's output times the token's gate weight for it, which is zero for
the experts outside its top k, one expert at a time; that is the same sum.

The weights are the bf16 leaves the harness made, in the layout it stores
them: the dense layers in `prefix`, the MoE layers stacked in `blocks/b0`;
w_q (d, H, nope + rope), w_dkv (d, kv_lora + rope), w_uk (kv_lora, H,
nope), w_uv (kv_lora, H, v), w_o (H, v, d), kv_norm/scale (kv_lora,), router
(d, E), routed w_gate/w_up (E, d, f), w_down (E, f, d), shared and dense
w_gate/w_up (d, f), w_down (f, d).  Each layer is upcast to float32 on its
own.

`precision="fp8"` is the control: the same pass with every matmul operand
rounded to float8 e4m3 (activations scaled per row, weights per tensor) —
the precision below the configuration's bf16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512        # sequences are padded to a multiple of this (causal: the
                 # padding cannot reach an earlier position), so a handful of
                 # shapes compile


def _fp8(x, axis):
    """x rounded to float8 e4m3 under a scale that maps its largest
    magnitude along `axis` (None: the whole tensor) to e4m3's largest."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = 448.0 / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, w, precision: str, contract: int = 1):
    """a (..., K) @ w (K..., N...) over the last `contract` dims of a."""
    if precision == "fp8":
        a = _fp8(a, axis=-1)
        w = _fp8(w, axis=None)
    ka = "".join("klmn"[i] for i in range(contract))
    rest = "".join("pqr"[i] for i in range(w.ndim - contract))
    return jnp.einsum(f"...{ka},{ka}{rest}->...{rest}", a, w)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rope_tables(dim: int, base: float, scaling):
    """(inv_freq (dim/2,), cos/sin factor), as transformers'
    `DeepseekV2YarnRotaryEmbedding` (float32) or, without `rope_scaling`,
    `DeepseekV2RotaryEmbedding`."""
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = (1.0 / base ** exps).astype(np.float32)
    if scaling is None:
        return freq_extra, 1.0
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr_dim(scaling.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    freq_inter = (1.0 / (factor * base ** exps)).astype(np.float32)
    mask = 1.0 - ramp
    inv = (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)
    m = (_yarn_mscale(factor, scaling.get("mscale", 1))
         / _yarn_mscale(factor, scaling.get("mscale_all_dim", 0)))
    return inv, m


def _rope(x, inv_freq, m):
    """x (T, heads, d) at positions 0..T-1: de-interleave the lanes, then
    rotate-half with cos/sin of cat(freqs, freqs) times m."""
    T, h, d = x.shape
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    x = x.reshape(T, h, d // 2, 2).swapaxes(-1, -2).reshape(T, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _swiglu(h, m, precision):
    g = _mm(h, m["w_gate"], precision)
    u = _mm(h, m["w_up"], precision)
    return _mm(jax.nn.silu(g) * u, m["w_down"], precision)


def _attention(h, a, *, eps, scale, inv_freq, m, precision):
    T = h.shape[0]
    nope = a["w_uk"].shape[-1]
    r = a["w_uk"].shape[0]
    q = _mm(h, a["w_q"], precision)                     # (T, H, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    lat = _mm(h, a["w_dkv"], precision)                 # (T, r + rope)
    c = _rmsnorm(lat[:, :r], a["kv_norm"]["scale"], eps)
    k_pe = lat[:, None, r:]
    k_nope = _mm(c, a["w_uk"], precision)               # (T, H, nope)
    v = _mm(c, a["w_uv"], precision)                    # (T, H, v)
    q_pe, k_pe = _rope(q_pe, inv_freq, m), _rope(k_pe, inv_freq, m)
    H = q.shape[1]
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, (T, H, k_pe.shape[-1]))], -1)
    if precision == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, None)
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    if precision == "fp8":
        p = _fp8(p, -1)
    o = jnp.einsum("hts,shd->thd", p, v)
    return _mm(o, a["w_o"], precision, contract=2)


def _moe(h, w, *, top_k, norm_topk, scaling_factor, precision):
    logits = _mm(h, w["router"], precision)             # float32, as published
    scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(scores, top_k)
    if top_k > 1 and norm_topk:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    else:
        top_w = top_w * scaling_factor
    E = scores.shape[-1]
    # (T, E): the token's weight for each expert, zero outside its top k
    gate = jnp.sum(jax.nn.one_hot(top_i, E, dtype=h.dtype)
                   * top_w[..., None], axis=1)

    def expert(y, e):
        ex = {k: w[k][e] for k in ("w_gate", "w_up", "w_down")}
        return y + gate[:, e, None] * _swiglu(h, ex, precision), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
    return y + _swiglu(h, w["shared"], precision)


@functools.partial(jax.jit, static_argnames=(
    "eps", "scale", "m", "top_k", "norm_topk", "scaling_factor",
    "precision"))
def _layer(x, w, inv_freq, *, eps, scale, m, top_k, norm_topk,
           scaling_factor, precision):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = _rmsnorm(x, w["ln1"]["scale"], eps)
    x = x + _attention(h, w["attn"], eps=eps, scale=scale,
                       inv_freq=inv_freq, m=m, precision=precision)
    h = _rmsnorm(x, w["ln2"]["scale"], eps)
    if "mlp" in w:
        return x + _swiglu(h, w["mlp"], precision)
    return x + _moe(h, w["moe"], top_k=top_k, norm_topk=norm_topk,
                    scaling_factor=scaling_factor, precision=precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, w_out, *, eps, precision):
    h = _rmsnorm(x, norm.astype(jnp.float32), eps)
    return _mm(h, w_out.astype(jnp.float32).T, precision)


def softmax_scale(hf: dict) -> float:
    """(nope + rope)^-0.5, times mscale(factor, mscale_all_dim)^2 under
    YaRN (transformers' `DeepseekV2Attention.softmax_scale`)."""
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    s = hf.get("rope_scaling")
    if s is not None and s.get("mscale_all_dim", 0):
        ms = _yarn_mscale(s["factor"], s["mscale_all_dim"])
        scale = scale * ms * ms
    return scale


def logits(hf: dict, params, tokens, positions, *, precision: str = "f32"
           ) -> np.ndarray:
    """float32 logits (len(positions), vocab) of the sequence `tokens` at
    `positions` (each sees tokens[:position + 1])."""
    eps = float(hf["rms_norm_eps"])
    inv_freq, m = _rope_tables(hf["qk_rope_head_dim"],
                               float(hf["rope_theta"]),
                               hf.get("rope_scaling"))
    static = dict(eps=eps, scale=softmax_scale(hf), m=float(m),
                  top_k=hf["num_experts_per_tok"],
                  norm_topk=bool(hf["norm_topk_prob"]),
                  scaling_factor=float(hf["routed_scaling_factor"]),
                  precision=precision)
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    ids = np.zeros(Tp, np.int32)
    ids[:T] = tokens
    dense = hf["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["embedding"], jnp.asarray(ids), axis=0
                     ).astype(jnp.float32)
        for layer in range(hf["num_hidden_layers"]):
            w = (params["prefix"][layer] if layer < dense else jax.tree.map(
                lambda a: a[layer - dense], params["blocks"]["b0"]))
            x = _layer(x, w, jnp.asarray(inv_freq), **static)
        w_out = (params["embed"]["embedding"] if hf["tie_word_embeddings"]
                 else params["lm_head"]["w_out"])
        n = len(positions)
        pos = np.zeros(-(-n // 128) * 128, np.int32)
        pos[:n] = positions
        out = _head(x[jnp.asarray(pos)], params["final_norm"]["scale"],
                    w_out, eps=eps, precision=precision)
    return np.asarray(out[:n], np.float32)
