"""Shared NN layers: norms, RoPE, MLPs, embeddings.

Parameter convention: every module exposes
  *_specs(cfg...) -> pytree of jax.ShapeDtypeStruct   (used by the dry-run)
and params are materialized from specs by `init_from_specs` (smoke tests /
real training only).  Math runs in f32 where it matters (norms, softmax,
router, rotary), weights are stored in cfg.dtype (bf16 by default).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import dense

Pytree = object


# ---------------------------------------------------------------------------
# spec helpers
# ---------------------------------------------------------------------------

def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def init_from_specs(specs: Pytree, key: jax.Array, scale: float = 0.02) -> Pytree:
    """Materialize params from a spec tree: truncated-normal(0, scale) for
    >=2D weights, ones for '*scale*' (norm) leaves, zeros for biases."""
    # jax.tree.flatten_with_path only exists on newer jax; the tree_util
    # spelling works across the versions we support.
    leaves, treedef = jax.tree_util.tree_flatten_with_path(specs)
    keys = jax.random.split(key, max(1, len(leaves)))
    out = []
    for (path, spec), k in zip(leaves, keys):
        name = jax.tree_util.keystr((path[-1],)) if path else ""
        if "scale" in name or "norm_w" in name:
            out.append(jnp.ones(spec.shape, spec.dtype))
        elif "bias" in name or spec.ndim < 2:
            out.append(jnp.zeros(spec.shape, spec.dtype))
        else:
            w = jax.random.truncated_normal(k, -2.0, 2.0, spec.shape, jnp.float32)
            out.append((w * scale).astype(spec.dtype))
    return jax.tree.unflatten(treedef, out)


def stack_specs(specs: Pytree, n: int) -> Pytree:
    """Prepend a stacking dim of size n to every leaf (scan-over-layers)."""
    return jax.tree.map(lambda s: sds((n, *s.shape), s.dtype), specs)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_specs(d: int, dtype) -> Pytree:
    return {"scale": sds((d,), dtype)}


def rmsnorm(p: Pytree, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature term, transformers' `yarn_get_mscale`:
    0.1 * mscale * ln(factor) + 1 (1 when the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(hd: int, theta: float, s) -> np.ndarray:
    """(hd/2,) YaRN rotary frequencies (transformers'
    `DeepseekV2YarnRotaryEmbedding`): each dim's frequency is blended
    between theta^(-2j/hd) and that over `factor` by a linear ramp between
    the dims that turn `beta_fast` and `beta_slow` times over the original
    context; dims below the ramp keep their frequency, dims above it are
    interpolated."""
    extra = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)

    def dim_of(rotations):
        return (hd * math.log(s.original_max_position_embeddings
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(s.beta_fast)), 0)
    high = min(math.ceil(dim_of(s.beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / s.factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         scaling=None, interleave: bool = False) -> jnp.ndarray:
    """Apply rotary embedding.  x: (..., S, H, hd); positions: (..., S).

    Rotate-half: lane j turns with lane j + hd/2.  `interleave` pairs lanes
    2j and 2j+1 instead, laid out de-interleaved on the way out (lanes 0, 2,
    4, .. then 1, 3, ..), as DeepSeek-V2 does; q and k share the layout, so
    their products are those of the pairing.  `scaling` (a
    `configs.base.YarnScaling`) takes YaRN's frequencies and its cos/sin
    factor mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    hd = x.shape[-1]
    half = hd // 2
    if scaling is None:
        freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                        * (jnp.log(theta) / half))
    else:
        freqs = jnp.asarray(yarn_inv_freq(hd, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_specs(d: int, f: int, dtype, act: str) -> Pytree:
    if act == "swiglu":
        return {"w_gate": sds((d, f), dtype), "w_up": sds((d, f), dtype),
                "w_down": sds((f, d), dtype)}
    return {"w_up": sds((d, f), dtype), "w_down": sds((f, d), dtype)}


def mlp(p: Pytree, x: jnp.ndarray, act: str, dense_mode: str = "ref") -> jnp.ndarray:
    """MLP with every projection routed through `kernels.ops.dense`, so the
    streaming GPP matmul (fused activation epilogue included) takes over on
    TPU at large shapes; "ref" mode reproduces the plain-jnp math exactly."""
    if act == "swiglu":
        h = (dense(x, p["w_gate"], activation="silu", mode=dense_mode)
             * dense(x, p["w_up"], mode=dense_mode))
    else:
        h = dense(x, p["w_up"], activation="gelu", mode=dense_mode)
    return dense(h, p["w_down"], mode=dense_mode)


# ---------------------------------------------------------------------------
# embeddings / lm head
# ---------------------------------------------------------------------------

def embed_specs(vocab: int, d: int, dtype) -> Pytree:
    return {"embedding": sds((vocab, d), dtype)}


def embed(p: Pytree, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed(p: Pytree, x: jnp.ndarray) -> jnp.ndarray:
    """Logits in f32 (softmax stability at 100k+ vocabs)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      p["embedding"].astype(jnp.float32))


def lm_head_specs(vocab: int, d: int, dtype) -> Pytree:
    return {"w_out": sds((vocab, d), dtype)}


def lm_head(p: Pytree, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      p["w_out"].astype(jnp.float32))


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean token NLL; logits f32 (B, S, V), labels int (B, S).

    The label logit is picked with a broadcast-compare + reduce (instead of
    take_along_axis) so XLA keeps the op fused and shardable when V is
    sharded over the model axis."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    onehot = labels[..., None] == jax.lax.broadcasted_iota(
        labels.dtype, (1,) * labels.ndim + (V,), labels.ndim)
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return jnp.mean(lse - ll)


def cross_entropy_chunked(head_fn, x: jnp.ndarray, labels: jnp.ndarray,
                          chunk: int = 512) -> jnp.ndarray:
    """Mean token NLL without materializing the full (B, S, V) logits.

    Scans sequence chunks; each chunk projects to logits, reduces to a scalar
    partial loss, and is rematerialized in backward (jax.checkpoint), so peak
    memory is one chunk of logits instead of the whole sequence — the
    difference between ~40 GiB and ~300 MiB per device at 151k vocab."""
    B, S, D = x.shape
    if S % chunk:
        chunk = S  # fall back to unchunked for odd sizes (smoke tests)
    n = S // chunk
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)          # (n, B, chunk, D)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(acc, inp):
        xc, lc = inp
        lg = head_fn(xc)                                    # (B, chunk, V) f32
        lse = jax.nn.logsumexp(lg, axis=-1)
        V = lg.shape[-1]
        onehot = lc[..., None] == jax.lax.broadcasted_iota(lc.dtype, (1, 1, V), 2)
        ll = jnp.sum(jnp.where(onehot, lg, 0.0), axis=-1)
        return acc + jnp.sum(lse - ll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (B * S)
