"""Mixture-of-Experts layer with sort-based token dispatch.

Why sort-based: the GShard one-hot dispatch einsum ((T,E,C) x (T,D)) books
2*T*E*C*D fake FLOPs into the HLO — it would poison the roofline compute
term.  Here dispatch is gather/scatter (bytes, not FLOPs), and expert FFNs
are batched einsums over (E, C, D) — HLO FLOPs == active-expert FLOPs, which
is what 6*N_active*D accounting expects.

Capacity: in training C = ceil(T * top_k / E * capacity_factor) and
overflow tokens are dropped (their combine weight contributes 0) — standard
practice.  The serving steps run dropless (`MoeConfig.dropless`): C is the
group's token count, which holds every token since a token picks an expert
at most once, so a served token never depends on its batch-mates.

EP sharding: the (E, C, D) dispatch buffer and (E, D, F) expert weights are
sharded over the `model` axis on E; XLA inserts the token all-to-alls at the
resharding boundaries.  The expert weight stack is also the paper's flagship
streaming workload (weights >> on-chip memory) — see core/streamer.py.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels.ops import dense, dense_grouped
from repro.models.attention import ambient_constraint
from repro.models.layers import sds


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int                      # per-expert hidden
    num_experts: int
    experts_per_token: int
    num_shared_experts: int = 0
    shared_d_ff: int | None = None # defaults to d_ff * num_shared
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True    # renormalise the top-k gate weights
    dropless: bool = False         # capacity = the group's tokens (serving)
    act: str = "swiglu"
    router_dtype: object = jnp.float32
    dtype: object = jnp.bfloat16
    dispatch_groups: int = 16      # token groups (aligned to the data axis)
    ep_mode: str = "tp"            # tp | dp (see configs/base.py)
    serve_resident: bool = False   # decode: resident E:model x d_ff:data
    dense_kernel: str = "auto"     # kernels.ops.dense/dense_grouped routing
                                   # for router, expert FFNs, shared experts


def moe_specs(c: MoeConfig):
    sp = {
        "router": sds((c.d_model, c.num_experts), c.dtype),
        "w_gate": sds((c.num_experts, c.d_model, c.d_ff), c.dtype),
        "w_up": sds((c.num_experts, c.d_model, c.d_ff), c.dtype),
        "w_down": sds((c.num_experts, c.d_ff, c.d_model), c.dtype),
    }
    if c.num_shared_experts:
        f = c.shared_d_ff or c.d_ff * c.num_shared_experts
        sp["shared"] = {
            "w_gate": sds((c.d_model, f), c.dtype),
            "w_up": sds((c.d_model, f), c.dtype),
            "w_down": sds((f, c.d_model), c.dtype),
        }
    return sp


def capacity(c: MoeConfig, num_tokens: int) -> int:
    """Slots per expert for a group of `num_tokens`: all of them when
    dropless, else the capacity factor's share (at least 8)."""
    if c.dropless:
        return num_tokens
    cap = math.ceil(num_tokens * c.experts_per_token / c.num_experts
                    * c.capacity_factor)
    return max(8, int(cap))


def _dispatch_groups(c: MoeConfig, T: int) -> int:
    g = c.dispatch_groups
    while g > 1 and T % g:
        g //= 2
    return max(1, g)


def expert_rows(c: MoeConfig, T: int) -> int:
    """Rows each routed-expert matmul multiplies for T tokens on the local
    path (`moe_apply` without a mesh): experts x groups x capacity."""
    G = _dispatch_groups(c, T)
    return c.num_experts * G * capacity(c, T // G)


def _dispatch(p, c: MoeConfig, xt: jnp.ndarray, C: int):
    """Route + scatter one token group into its (E, C, D) buffer (LOCAL —
    the group is a data shard).  Returns (buf, combine metadata)."""
    Tg, D = xt.shape
    k, E = c.experts_per_token, c.num_experts

    logits = dense(xt.astype(c.router_dtype),
                   p["router"].astype(c.router_dtype), mode=c.dense_kernel)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                      # (Tg, k)
    if c.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_e.reshape(-1)                                  # (Tg*k,)
    order = jnp.argsort(flat_e)                                 # stable
    sorted_e = flat_e[order]
    grp_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    slot = jnp.arange(Tg * k) - grp_start[sorted_e]
    keep = slot < C
    token_idx = order // k

    buf = jnp.zeros((E, C, D), xt.dtype)
    buf = buf.at[sorted_e, jnp.where(keep, slot, 0)].add(
        jnp.where(keep[:, None], xt[token_idx], 0).astype(xt.dtype))
    w = top_p.reshape(-1)[order]
    return buf, (sorted_e, slot, keep, token_idx, w)


def _grouped_ffn(wg, wu, wd, buf: jnp.ndarray, act: str, mode: str) -> jnp.ndarray:
    """(E, C, D) -> (E, C, D) per-expert FFN through `dense_grouped`: the
    expert weight stack streams under the GPP batched-expert schedule on
    TPU; "ref" routing reproduces the plain batched einsums exactly."""
    if act == "swiglu":
        h = (dense_grouped(buf, wg, activation="silu", mode=mode)
             * dense_grouped(buf, wu, mode=mode))
    else:
        h = dense_grouped(buf, wu, activation="gelu", mode=mode)
    return dense_grouped(h, wd, mode=mode)


def _expert_ffn(p, c: MoeConfig, buf: jnp.ndarray) -> jnp.ndarray:
    """(G, E, C, D) -> (G, E, C, D) expert FFN (grouped streaming matmuls).

    tp mode: expert weights are EP-sharded over `model` and FSDP-sharded
    over `data`.  We GATHER the data shards explicitly before the matmuls —
    the paper's write/compute streaming — because letting the partitioner
    handle the sharded contraction dim makes it all-reduce f32 ACTIVATIONS
    over data instead.  On kimi-k2 the weights cost 2 GB/layer (bf16); the
    activations 30+ GB (count).

    The token-group axis G folds into the per-expert row dim (E, G*C, D) so
    each expert's weights stream from HBM once for ALL groups — the grouped
    kernel's outer-ring expert axis."""
    P = jax.sharding.PartitionSpec
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if c.ep_mode == "tp":
        wg = ambient_constraint(wg, P("model", None, None))
        wu = ambient_constraint(wu, P("model", None, None))
        wd = ambient_constraint(wd, P("model", None, None))
    # NB: under an ambient SPMD mesh, dense_grouped's "auto" routing falls
    # back to "ref" (ops._ambient_mesh_active) — pallas_call on these
    # EP/FSDP-sharded stacks would force XLA to all-gather them in full.
    G, E, C, D = buf.shape
    rows = buf.swapaxes(0, 1).reshape(E, G * C, D)
    out = _grouped_ffn(wg, wu, wd, rows, c.act, c.dense_kernel)
    return out.reshape(E, G, C, D).swapaxes(0, 1)


def _combine(out_buf, meta, Tg: int, dtype):
    """Gather expert outputs back to token order (LOCAL per group)."""
    sorted_e, slot, keep, token_idx, w = meta
    gathered = out_buf[sorted_e, jnp.where(keep, slot, 0)]      # (Tg*k, D)
    gathered = jnp.where(keep[:, None],
                         gathered * w[:, None].astype(gathered.dtype), 0)
    # combine in the storage dtype: k<=8 contributions, and f32 here would
    # psum a 4x-bigger tensor across ranks
    return jnp.zeros((Tg, gathered.shape[-1]), dtype).at[token_idx].add(
        gathered.astype(dtype))


def _routed_local(p_routed, c: MoeConfig, xt, C: int, n_local: int):
    """Per-(data x model) shard: dispatch local tokens, run THIS model rank's
    expert slice, combine partials.  Caller psums over `model`."""
    wg, wu, wd = p_routed["w_gate"], p_routed["w_up"], p_routed["w_down"]
    buf, meta = _dispatch(p_routed, c, xt, C)          # (E, C, D) local tokens
    # slice this model rank's experts out of the replicated dispatch
    idx = jax.lax.axis_index("model")
    bufe = jax.lax.dynamic_slice_in_dim(buf, idx * n_local, n_local, 0)
    out_e = _grouped_ffn(wg, wu, wd, bufe, c.act, c.dense_kernel)  # (E_local, C, D)
    # place back into the full-E frame so the combine gather stays simple
    out_buf = jnp.zeros((c.num_experts, C, out_e.shape[-1]), out_e.dtype)
    out_buf = jax.lax.dynamic_update_slice_in_dim(out_buf, out_e, idx * n_local, 0)
    partial = _combine(out_buf, meta, xt.shape[0], xt.dtype)
    return jax.lax.psum(partial, "model")              # (Tg, D)


def _moe_shard_map(p, c: MoeConfig, x: jnp.ndarray, mesh) -> jnp.ndarray:
    """Explicit-schedule routed experts (shard_map over data x model).

    The paper's write/compute structure made literal: the per-layer
    `all_gather` of the data-sharded expert weights is the "rewrite", the
    expert einsums the "compute"; bwd transposes to reduce-scatter.  We use
    shard_map because the SPMD partitioner's implicit choices for this block
    (activation psums fwd, replicate-then-slice bwd) move far more bytes
    than the weight gathers (the count in `_expert_ffn`)."""
    from jax.sharding import PartitionSpec as P
    B, S, D = x.shape
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    T_local = B * S // dp_size
    C = capacity(c, T_local)
    n_local = c.num_experts // tp

    def local(xb, router, wg, wu, wd):
        xt = xb.reshape(-1, D)
        # the "rewrite": gather this rank's expert slice over the fsdp axis
        wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, "data", axis=1, tiled=True)
        pr = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        out = _routed_local(pr, c, xt, C, n_local)
        return out.reshape(xb.shape)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", "data", None)),
        out_specs=P(dp, None, None),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_shard_map_serve(p, c: MoeConfig, x: jnp.ndarray, mesh) -> jnp.ndarray:
    """Decode-time routed experts with RESIDENT weights: E sharded over
    `model`, d_ff sharded over `data` — no weight movement at all.  Tokens
    (tiny at decode: B x 1) are replicated inside the block; each shard
    computes its (E_local x F_slice) partial and one small psum over
    (data, model) combines.  kimi-k2: ~44 MB psum/layer vs 2.1 GB of weight
    gathers per token (count)."""
    from jax.sharding import PartitionSpec as P
    B, S, D = x.shape
    T = B * S
    C = capacity(c, T)
    tp = mesh.shape.get("model", 1)
    n_local = c.num_experts // tp

    def local(xb, router, wg, wu, wd):
        xt = xb.reshape(T, D)
        buf, meta = _dispatch({"router": router}, c, xt, C)
        idx = jax.lax.axis_index("model")
        bufe = jax.lax.dynamic_slice_in_dim(buf, idx * n_local, n_local, 0)
        out_e = _grouped_ffn(wg, wu, wd, bufe, c.act, c.dense_kernel)  # F-slice partial
        out_buf = jnp.zeros((c.num_experts, C, D), out_e.dtype)
        out_buf = jax.lax.dynamic_update_slice_in_dim(
            out_buf, out_e, idx * n_local, 0)
        partial = _combine(out_buf, meta, T, xt.dtype)
        # experts over model + d_ff slices over data; NOT pod (weights are
        # replicated across pods — summing there would double-count)
        partial = jax.lax.psum(partial, ("model", "data"))
        return partial.reshape(B, S, D)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None, None), P(None, None),
                  P("model", None, "data"), P("model", None, "data"),
                  P("model", "data", None)),
        out_specs=P(None, None, None),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _mesh_dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if mesh is not None and not mesh.empty and a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _mesh_has(mesh, *axes) -> bool:
    return mesh is not None and not mesh.empty and all(
        a in mesh.axis_names for a in axes)


def moe_apply(p, c: MoeConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).

    With a (data, model) mesh ambient, the routed experts run under an
    explicit shard_map schedule (`_moe_shard_map`).  Without one (CPU smoke
    tests), dispatch is grouped and everything stays local."""
    B, S, D = x.shape
    T = B * S
    mesh = jax.sharding.get_abstract_mesh()
    use_sm = (_mesh_has(mesh, "data", "model")
              and c.num_experts % mesh.shape.get("model", 1) == 0
              and T % max(1, _mesh_dp_size(mesh)) == 0)
    use_serve = (c.serve_resident and _mesh_has(mesh, "data", "model")
                 and c.num_experts % mesh.shape.get("model", 1) == 0
                 and T <= 4096)  # tokens replicated inside: decode-sized only
    if use_serve:
        out = _moe_shard_map_serve(p, c, x, mesh)
    elif use_sm:
        out = _moe_shard_map(p, c, x, mesh)
    else:
        G = _dispatch_groups(c, T)
        Tg = T // G
        C = capacity(c, Tg)
        xg = x.reshape(G, Tg, D)
        buf, meta = jax.vmap(lambda xt: _dispatch(p, c, xt, C))(xg)
        out_buf = _expert_ffn(p, c, buf)
        out = jax.vmap(lambda ob, m: _combine(ob, m, Tg, x.dtype))(out_buf, meta)
        out = out.reshape(B, S, D)

    if c.num_shared_experts:
        xt = x.reshape(T, D)
        sh = p["shared"]
        if c.act == "swiglu":
            hs = (dense(xt, sh["w_gate"], activation="silu", mode=c.dense_kernel)
                  * dense(xt, sh["w_up"], mode=c.dense_kernel))
        else:
            hs = dense(xt, sh["w_up"], activation="gelu", mode=c.dense_kernel)
        out = out + dense(hs, sh["w_down"], mode=c.dense_kernel).reshape(B, S, D)

    return out


def aux_load_balance_loss(p, c: MoeConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Switch-style load-balance auxiliary loss (fraction * probability)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    logits = dense(xt.astype(c.router_dtype),
                   p["router"].astype(c.router_dtype), mode=c.dense_kernel)
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jnp.argmax(probs, axis=-1)
    frac = jnp.bincount(top_e, length=c.num_experts).astype(jnp.float32) / xt.shape[0]
    mean_p = jnp.mean(probs, axis=0)
    return c.num_experts * jnp.sum(frac * mean_p)
