"""Plain float32 reference of a Qwen2-architecture decoder (Qwen2, Qwen1.5):
the published forward pass in `jax.numpy`, for one sequence at a time, with
no kernel, no cache and no batching.  It imports nothing of the program.

Per layer, as in the published model (transformers' Qwen2 modelling code):
RMSNorm (eps from the config) -> q, k, v projections with biases -> RoPE
(rotate-half, theta from the config) on q and k -> causal attention with
grouped KV heads, softmax in float32, scale 1/sqrt(head_dim) -> output
projection -> residual; RMSNorm -> SwiGLU MLP (silu(x Wg) * x Wu) Wd ->
residual.  Then a final RMSNorm and the LM head (the embedding's transpose
when tied).  Departures: none in the mathematics.

The weights are the bf16 leaves the harness made, in the layout it stores
them (`blocks/b0/...` stacked over layers; w_q (d, H, hd), w_o (H, hd, d),
w_gate/w_up (d, f), w_down (f, d), embedding and w_out (vocab, d)).  Each
layer is upcast to float32 on its own, so a 7B-wide layer at a time fits.

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


def _rope(x, theta):
    """x (T, heads, hd) at positions 0..T-1, rotate-half convention."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("eps", "theta", "precision"))
def _layer(x, w, *, eps, theta, precision):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a, m = w["attn"], w["mlp"]
    T = x.shape[0]
    h = _rmsnorm(x, w["ln1"]["scale"], eps)
    q = _rope(_mm(h, a["w_q"], precision) + a["b_q"], theta)
    k = _rope(_mm(h, a["w_k"], precision) + a["b_k"], theta)
    v = _mm(h, a["w_v"], precision) + a["b_v"]
    H, KVH, hd = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    if precision == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, None)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    if precision == "fp8":
        p = _fp8(p, -1)
    o = jnp.einsum("hts,shd->thd", p, v)
    x = x + _mm(o, a["w_o"], precision, contract=2)
    h = _rmsnorm(x, w["ln2"]["scale"], eps)
    g = _mm(h, m["w_gate"], precision)
    u = _mm(h, m["w_up"], precision)
    return x + _mm(jax.nn.silu(g) * u, m["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, w_out, *, eps, precision):
    h = _rmsnorm(x, norm.astype(jnp.float32), eps)
    return _mm(h, w_out.astype(jnp.float32).T, precision)


def logits(hf: dict, params, tokens, positions, *, precision: str = "f32"
           ) -> np.ndarray:
    """float32 logits (len(positions), vocab) of the sequence `tokens` at
    `positions` (each sees tokens[:position + 1])."""
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    T = len(tokens)
    Tp = -(-T // PAD) * PAD
    ids = np.zeros(Tp, np.int32)
    ids[:T] = tokens
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["embedding"], jnp.asarray(ids), axis=0
                     ).astype(jnp.float32)
        blocks = params["blocks"]["b0"]
        for layer in range(hf["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[layer], blocks)
            x = _layer(x, w, eps=eps, theta=theta, precision=precision)
        w_out = (params["embed"]["embedding"] if hf["tie_word_embeddings"]
                 else params["lm_head"]["w_out"])
        n = len(positions)
        pos = np.zeros(-(-n // 128) * 128, np.int32)
        pos[:n] = positions
        out = _head(x[jnp.asarray(pos)], params["final_norm"]["scale"],
                    w_out, eps=eps, precision=precision)
    return np.asarray(out[:n], np.float32)
