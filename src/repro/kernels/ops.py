"""Jit'd public wrappers for the Pallas kernels.

`streamed_matmul` picks the ring depth from the same GPP planner that the
paper's analytic model validates (`repro.core.schedule.plan_stream`), using
the target chip's peaks (`core.schedule.CHIPS`).  On a TPU v5e a
(block_k, bn) bf16 tile moves block_k*bn*2 bytes at ~819 GB/s HBM while the
MXU computes 2*M*block_k*bn flops at ~197 TFLOP/s, so t_dma/t_compute ≈
120/M for bf16 — small M (the paper's small-n_in regime) is exactly where
deep rings win.

`dense` is the single model-facing matmul entry point for the whole model
zoo.  Routing table (who calls it, with what weight layout):

  models/layers.py   mlp w_up/w_gate/w_down        (D, F) 2-D weights
  models/attention.py  gqa/mha q/k/v  (D, H, hd)   contract_dims=1
                       o-proj         (H, hd, D)   contract_dims=2
                       MLA w_dq/w_dkv (D, R)       2-D
                           w_uq/w_uk/w_uv (R, H, hd)  contract_dims=1
                           w_o        (H, hd, D)   contract_dims=2
                       cross-attn q/k/v/o          as gqa
  models/ssm.py        w_in/w_bc/w_dt/w_out        2-D
  models/xlstm.py      mlstm q/k/v (D, H, hd), w_o (H, hd, D), gates,
                       slstm z/i/f/og/out          2-D
  models/moe.py        router/shared experts via `dense`; routed expert
                       FFNs via `dense_grouped` (E, D, F) batched weights

The einsum-shaped projection adapter: leading dims of x are flattened, the
last `contract_dims` dims of x contract against the first `contract_dims`
dims of w (reshaped to 2-D), and both are restored on the way out — so
`dhk`/`hkd`-style projection tensors stream through the same GPP schedule
as plain 2-D matmuls.  The matmul routes either through the streaming
Pallas kernel (TPU backend, weight large enough to be worth streaming) or
through the fused-epilogue jnp reference (CPU / tiny weights).  The "ref"
mode reproduces plain `act(x @ w)` math bit-for-bit so existing model
numerics are unchanged when the kernel is off.

`dense_grouped` is the MoE batched-expert variant: (E, C, D) @ (E, D, F)
with the expert axis as the outermost ring dimension of the streaming
schedule, so each expert's weights cross the HBM link exactly once per
step and the ring pipelines across experts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.schedule import V5E, ChipSpec, plan_stream
from repro.kernels.gpp_matmul import (_ACTIVATIONS, gpp_matmul,
                                      gpp_matmul_grouped, target_chip)
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import dense_grouped_ref, dense_ref, paged_attn_ref

# below this weight size the DMA pipeline cannot beat a resident matmul
DENSE_KERNEL_MIN_BYTES = 1 * 1024 * 1024

# shared by `dense` and `dense_grouped` (the grouped path accepts the same
# four modes; "kernel"/"interpret" route through gpp_matmul_grouped)
DENSE_MODES = ("auto", "ref", "kernel", "interpret")

# paged-attention read-path routing (cfg.paged_attn_kernel): "pallas" is the
# compiled block-table kernel, "interpret" the same kernel on the CPU
# interpreter, "ref" the exact gather+_sdpa math the serving engine shipped
# with, "auto" picks pallas on TPU and ref elsewhere (like `dense`'s auto).
PAGED_ATTN_MODES = ("auto", "ref", "pallas", "interpret")


def plan_ring_depth(M: int, K: int, block_n: int, dtype=jnp.bfloat16,
                    max_ring: int = 8, chip: ChipSpec = V5E) -> int:
    """Ring depth G = ceil(t_dma / t_compute) + 1 for one weight tile."""
    itemsize = jnp.dtype(dtype).itemsize
    plan = plan_stream(
        block_bytes=K * block_n * itemsize,
        compute_flops=2.0 * M * K * block_n,
        flops_per_s=chip.peak_flops,
        transfer_bytes_per_s=chip.hbm_bytes_per_s,
        max_ring=max_ring,
    )
    return plan.ring_depth


@functools.partial(jax.jit, static_argnames=(
    "activation", "block_m", "block_n", "block_k", "num_bufs", "interpret"))
def streamed_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: jnp.ndarray | None = None,
    w_scale: jnp.ndarray | None = None,
    activation: str | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    num_bufs: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = epilogue(x @ w) with HBM-streamed weights under the GPP DMA
    schedule, tiled over M/N/K by the VMEM-budget planner."""
    return gpp_matmul(
        x, w, bias=bias, w_scale=w_scale, activation=activation,
        block_m=block_m, block_n=block_n, block_k=block_k,
        num_bufs=num_bufs, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_n", "num_bufs", "interpret"))
def streamed_gemm_sequence(
    x: jnp.ndarray,
    ws: jnp.ndarray,
    *,
    block_n: int = 256,
    num_bufs: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """The paper's BLAS workload: consecutive GeMMs ys[r] = x @ ws[r] with
    every round's weights streamed from HBM.  The round dimension is folded
    into the streamed tile stream, so the ring pipelines *across* GeMMs just
    like macros pipeline across consecutive layers."""
    R, K, N = ws.shape
    w_flat = jnp.transpose(ws, (1, 0, 2)).reshape(K, R * N)
    if num_bufs is None:
        num_bufs = plan_ring_depth(x.shape[0], K, block_n, x.dtype,
                                   chip=target_chip(interpret))
    y = gpp_matmul(x, w_flat, block_n=block_n, num_bufs=num_bufs, interpret=interpret)
    M = x.shape[0]
    return jnp.transpose(y.reshape(M, R, N), (1, 0, 2))


def _ambient_mesh_active() -> bool:
    """True when an ambient SPMD mesh is set: `pallas_call` cannot be
    partitioned by GSPMD, so auto-mode must not route sharded global arrays
    into the kernel — XLA would all-gather the full weight onto every device
    (the exact traffic blowup the streaming path exists to avoid).  Callers
    that hold per-rank local arrays (inside shard_map) can still opt in with
    an explicit mode="kernel"."""
    return not jax.sharding.get_abstract_mesh().empty


def _targets_tpu(*arrays) -> bool:
    """Whether the computation will land on TPU: concrete arrays reveal
    their devices (every one must be on TPU); tracers (under jit) don't, so
    the process default backend decides.  Work explicitly pinned to CPU
    inside a jit on a TPU host can still mis-route — pass mode="ref"
    there."""
    concrete = [a for a in arrays if isinstance(a, jax.Array)
                and not isinstance(a, jax.core.Tracer)]
    if concrete:
        return all(d.platform == "tpu" for a in concrete for d in a.devices())
    return jax.default_backend() == "tpu"


def resolve_dense_mode(mode: str, w, *arrays) -> str:
    """The single routing policy for `dense` and `dense_grouped`: validates
    `mode` and resolves "auto" to kernel on TPU when w is in the streaming
    regime AND no ambient SPMD mesh would have to all-gather it into the
    (unpartitionable) pallas_call; else the bit-identical ref path.
    `arrays` are the call's other operands (their placement counts too)."""
    if mode not in DENSE_MODES:
        raise ValueError(f"dense mode must be one of {DENSE_MODES}, got {mode!r}")
    if mode != "auto":
        return mode
    w_bytes = w.size * w.dtype.itemsize
    return ("kernel" if _targets_tpu(w, *arrays)
            and w_bytes >= DENSE_KERNEL_MIN_BYTES
            and not _ambient_mesh_active() else "ref")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dense_kernel(activation, interpret, x2, w, bias, w_scale):
    """Kernel-path forward with a ref-math VJP: the Pallas kernel has no AD
    rule, so backward recomputes through the fused-epilogue oracle
    (`dense_ref`, same f32 math the kernel implements) — training under
    mode="auto"/"kernel" gets standard XLA matmul gradients while the
    forward keeps the streaming schedule."""
    return gpp_matmul(x2, w, bias=bias, w_scale=w_scale,
                      activation=activation, interpret=interpret)


def _dense_kernel_fwd(activation, interpret, x2, w, bias, w_scale):
    y = _dense_kernel(activation, interpret, x2, w, bias, w_scale)
    return y, (x2, w, bias, w_scale)


def _dense_kernel_bwd(activation, interpret, res, g):
    x2, w, bias, w_scale = res
    _, pullback = jax.vjp(
        lambda xx, ww, bb, ss: dense_ref(xx, ww, bias=bb, w_scale=ss,
                                         activation=activation),
        x2, w, bias, w_scale)
    return pullback(g)


_dense_kernel.defvjp(_dense_kernel_fwd, _dense_kernel_bwd)


def _dense_ref_path(x2: jnp.ndarray, w: jnp.ndarray, bias, activation):
    """Exact pre-kernel model math: act(x @ w [+ bias]) in the ambient dtype
    (no f32 round trip), so "ref" routing leaves existing models untouched."""
    y = x2 @ w
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return _ACTIVATIONS[activation](y)


def dense(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: jnp.ndarray | None = None,
    w_scale: jnp.ndarray | None = None,
    activation: str | None = None,
    mode: str = "auto",
    contract_dims: int = 1,
) -> jnp.ndarray:
    """act(x @ w [* w_scale] [+ bias]) over arbitrary leading dims of x.

    The projection adapter generalizes the matmul to einsum-shaped weights:
    the last `contract_dims` dims of x contract against the first
    `contract_dims` dims of w, and w's remaining dims shape the output —
    e.g. q-proj `bsd,dhk->bshk` is `dense(x, w_q)`, o-proj `bshk,hkd->bsd`
    is `dense(out, w_o, contract_dims=2)`.  bias (if any) must match w's
    output dims.

    mode:
      auto       kernel on TPU when w is at least DENSE_KERNEL_MIN_BYTES
                 (the streaming regime), else ref
      kernel     always the Pallas GPP kernel (compiled)
      interpret  the Pallas kernel in interpret mode (CPU validation)
      ref        fused jnp fallback (identical math to the pre-kernel models)
    """
    mode = resolve_dense_mode(mode, w, x)
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    if not 1 <= contract_dims <= min(x.ndim, w.ndim):
        raise ValueError(
            f"contract_dims={contract_dims} invalid for x{x.shape} @ w{w.shape}")
    cshape = w.shape[:contract_dims]
    if x.shape[-contract_dims:] != cshape:
        raise ValueError(
            f"contraction mismatch: x{x.shape} trailing dims vs w{w.shape} "
            f"leading dims (contract_dims={contract_dims})")
    out_dims = w.shape[contract_dims:]
    Kf = math.prod(cshape)
    Nf = math.prod(out_dims)
    lead = x.shape[:x.ndim - contract_dims]
    x2 = x.reshape(-1, Kf)
    w2 = w.reshape(Kf, Nf)
    if bias is not None:
        bias = bias.reshape(Nf)
    if mode == "ref":
        if w_scale is not None:
            w2 = (w2.astype(jnp.float32)
                  * jnp.asarray(w_scale, jnp.float32).reshape(1, -1)).astype(x.dtype)
        y2 = _dense_ref_path(x2, w2, bias, activation)
    else:
        y2 = _dense_kernel(activation, mode == "interpret", x2, w2, bias, w_scale)
    return y2.reshape(*lead, *out_dims)


# ---------------------------------------------------------------------------
# paged-attention entry point (serving read path)
# ---------------------------------------------------------------------------

def resolve_paged_attn_mode(mode: str, *arrays) -> str:
    """Resolve "auto" for the paged-attention read path: the Pallas kernel on
    TPU (pallas_call is not GSPMD-partitionable, so an ambient mesh falls
    back, mirroring `dense`'s auto policy), the exact gather math elsewhere.
    Returns one of "ref" | "pallas" | "interpret"."""
    if mode not in PAGED_ATTN_MODES:
        raise ValueError(
            f"paged_attn mode must be one of {PAGED_ATTN_MODES}, got {mode!r}")
    if mode != "auto":
        return mode
    return ("pallas" if _targets_tpu(*arrays) and not _ambient_mesh_active()
            else "ref")


def paged_attn(
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
    mode: str = "auto",
    num_bufs: "int | None" = None,
) -> jnp.ndarray:
    """Paged attention over shared block pools, routed like `dense`.

    q: (B, S, H, dk); pool_a/pool_b: (nb, bs, ...) physical pools;
    tables: (B, MB) int32 block table (0 = reserved null block);
    positions: (B,) int32 per-lane query start positions.

    GQA: pools are k/v, dk = head_dim, stored lane-dense as
    (nb, bs, KVH*hd) (`models.attention.paged_cache_specs`: XLA relaid a
    (nb, bs, KVH, hd) pool with hd under 128 lanes out around every write
    and read); (nb, bs, KVH, hd) pools are accepted too.  MLA
    (`mla=True`): pools are c_kv/k_rope, q is already absorbed through w_uk
    (dk = kv_lora + rope), and the return value is the latent output for
    the caller to up-project.
    A k_rope pool wider than q's rope lanes (zero-padded to whole 128-lane
    tiles, `models.attention.paged_cache_specs`) gets q zero-padded to
    match, so the padding adds nothing to the logits.

    mode:
      auto       pallas on TPU, else ref
      pallas     the streaming Pallas kernel (compiled)
      interpret  the same kernel on the interpreter (CPU validation)
      ref        gather through the tables + exact `_sdpa` math — the
                 pre-kernel serving read path, bit-for-bit
    """
    mode = resolve_paged_attn_mode(mode, q, pool_a, pool_b)
    if mla:
        pad = pool_a.shape[-1] + pool_b.shape[-1] - q.shape[-1]
        if pad:
            q = jnp.pad(q, [(0, 0)] * 3 + [(0, pad)])
    if mode == "ref":
        return paged_attn_ref(q, pool_a, pool_b, tables, positions,
                              num_kv_heads=num_kv_heads, scale=scale,
                              window=window, mla=mla)
    return paged_attention(q, pool_a, pool_b, tables, positions,
                           num_kv_heads=num_kv_heads, scale=scale,
                           window=window, mla=mla, num_bufs=num_bufs,
                           interpret=mode == "interpret")


# ---------------------------------------------------------------------------
# grouped (batched-expert) entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dense_grouped_kernel(activation, interpret, x3, w, bias, w_scale):
    """Grouped kernel-path forward with a ref-math VJP (see `_dense_kernel`):
    backward recomputes through `dense_grouped_ref`, the same f32 math the
    grouped kernel implements."""
    return gpp_matmul_grouped(x3, w, bias=bias, w_scale=w_scale,
                              activation=activation, interpret=interpret)


def _dense_grouped_kernel_fwd(activation, interpret, x3, w, bias, w_scale):
    y = _dense_grouped_kernel(activation, interpret, x3, w, bias, w_scale)
    return y, (x3, w, bias, w_scale)


def _dense_grouped_kernel_bwd(activation, interpret, res, g):
    x3, w, bias, w_scale = res
    _, pullback = jax.vjp(
        lambda xx, ww, bb, ss: dense_grouped_ref(xx, ww, bias=bb, w_scale=ss,
                                                 activation=activation),
        x3, w, bias, w_scale)
    return pullback(g)


_dense_grouped_kernel.defvjp(_dense_grouped_kernel_fwd, _dense_grouped_kernel_bwd)


def dense_grouped(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: jnp.ndarray | None = None,
    w_scale: jnp.ndarray | None = None,
    activation: str | None = None,
    mode: str = "auto",
) -> jnp.ndarray:
    """Per-expert act(x[e] @ w[e] [* w_scale[e]] [+ bias[e]]):
    (E, C, D) @ (E, D, F).

    The MoE companion to `dense`: the streaming plan treats the expert axis
    as the outermost ring dimension, so expert weights stream from HBM once
    per step and the ring pipelines across experts (the paper's
    consecutive-GeMM workload with per-round activations).  `w_scale`
    (scalar, (E,), or (E, F)) is the int8 dequant path: expert weights
    stream raw and the scale folds into the fused epilogue, mirroring the
    flat kernel.  Modes as in `dense`; "ref" reproduces the models' plain
    batched-einsum math bit-for-bit (dequant pre-scales the weights, like
    `dense`'s ref path).
    """
    mode = resolve_dense_mode(mode, w, x)
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    if x.ndim != 3 or w.ndim != 3:
        raise ValueError(f"dense_grouped wants (E,C,D) @ (E,D,F), "
                         f"got x{x.shape} w{w.shape}")
    if x.shape[0] != w.shape[0] or x.shape[2] != w.shape[1]:
        raise ValueError(f"grouped shape mismatch: x{x.shape} @ w{w.shape}")
    if mode == "ref":
        if w_scale is not None:
            sc = jnp.asarray(w_scale, jnp.float32)
            sc = sc if sc.ndim == 0 else sc.reshape(w.shape[0], 1, -1)
            w = (w.astype(jnp.float32) * sc).astype(x.dtype)
        y = jnp.einsum("ecd,edf->ecf", x, w)
        if bias is not None:
            y = y + bias[:, None, :].astype(y.dtype)
        return _ACTIVATIONS[activation](y)
    return _dense_grouped_kernel(activation, mode == "interpret", x, w, bias,
                                 w_scale)
