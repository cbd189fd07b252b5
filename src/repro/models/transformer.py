"""Backbone assembler: composes attention/MoE/SSM/xLSTM blocks into the ten
assigned architectures, with scan-over-superblocks and optional GPP weight
streaming (the paper's technique) on the stacked block weights.

Layer layout: `cfg.prefix_pattern` names unstacked leading layers (e.g. the
dense first layer of DeepSeek/Kimi MoEs); `cfg.pattern` is the repeating
superblock (e.g. gemma3's 5 local + 1 global) stacked `num_superblocks`
times and scanned.  "shared_attn" (Zamba2) uses one unstacked param set
reused by every superblock — the paper's weight-reuse limit case.

Entry points:
  param_specs / init_params
  loss_fn(params, cfg, batch)                      training forward + CE
  prefill(params, cfg, batch, max_len)             -> (logits, caches)
  decode_step(params, cfg, tokens, caches, pos)    -> (logits, caches)
  cache_specs(cfg, batch, max_len)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.streamer import StreamSettings, stream_layers
from repro.kernels.gpp_matmul import target_chip, weight_pad_bytes
from repro.kernels.ops import resolve_dense_mode
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    cross_entropy, cross_entropy_chunked, embed, embed_specs, init_from_specs, lm_head, lm_head_specs,
    mlp, mlp_specs, rmsnorm, rmsnorm_specs, sds, stack_specs, unembed,
)

Pytree = Any


# ---------------------------------------------------------------------------
# per-kind specs
# ---------------------------------------------------------------------------

def _attn_cfg(cfg: ModelConfig, kind: str) -> attn.AttnConfig:
    window = cfg.window_size if kind.endswith(":window") else None
    return attn.AttnConfig(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        rope_scaling=cfg.rope_scaling,
        rope_interleave=cfg.rope_interleave,
        window=window,
        kv_lora_rank=cfg.kv_lora_rank,
        q_lora_rank=cfg.q_lora_rank,
        rope_head_dim=cfg.rope_head_dim,
        dtype=cfg.jdtype,
        dense_mode=cfg.dense_kernel,
        paged_mode=cfg.paged_attn_kernel,
    )


def _ssm_cfg(cfg: ModelConfig) -> ssm_mod.SsmConfig:
    return ssm_mod.SsmConfig(
        d_model=cfg.d_model,
        d_inner=cfg.ssm_expansion * cfg.d_model,
        d_state=cfg.ssm_state_dim,
        n_heads=cfg.num_heads,
        dtype=cfg.jdtype,
        dense_mode=cfg.dense_kernel,
    )


def _xlstm_cfg(cfg: ModelConfig) -> xlstm_mod.XlstmConfig:
    return xlstm_mod.XlstmConfig(
        d_model=cfg.d_model, n_heads=cfg.num_heads, dtype=cfg.jdtype,
        dense_mode=cfg.dense_kernel,
    )


def _moe_cfg(cfg: ModelConfig, dropless: bool = False) -> moe_mod.MoeConfig:
    """The MoE layer's config.  `dropless`: the serving steps', whose
    capacity holds every token of a group (`moe.capacity`)."""
    return moe_mod.MoeConfig(
        d_model=cfg.d_model,
        d_ff=cfg.moe_d_ff or cfg.d_ff,
        num_experts=cfg.num_experts,
        experts_per_token=cfg.experts_per_token,
        num_shared_experts=cfg.num_shared_experts,
        capacity_factor=cfg.moe_capacity_factor,
        norm_topk_prob=cfg.moe_norm_topk_prob,
        dropless=dropless,
        act=cfg.act,
        dtype=cfg.jdtype,
        ep_mode=cfg.moe_ep_mode,
        serve_resident=cfg.moe_serve_resident,
        dense_kernel=cfg.dense_kernel,
    )


def block_specs(cfg: ModelConfig, kind: str) -> Pytree:
    d, dt = cfg.d_model, cfg.jdtype
    base = kind.split(":")[0]
    if base in ("dense", "shared_attn"):
        return {
            "ln1": rmsnorm_specs(d, dt),
            "attn": attn.attn_specs(_attn_cfg(cfg, kind)),
            "ln2": rmsnorm_specs(d, dt),
            "mlp": mlp_specs(d, cfg.d_ff, dt, cfg.act),
        }
    if base == "moe":
        return {
            "ln1": rmsnorm_specs(d, dt),
            "attn": attn.attn_specs(_attn_cfg(cfg, kind)),
            "ln2": rmsnorm_specs(d, dt),
            "moe": moe_mod.moe_specs(_moe_cfg(cfg)),
        }
    if base == "mamba":
        return {"ln": rmsnorm_specs(d, dt), "ssm": ssm_mod.ssm_specs(_ssm_cfg(cfg))}
    if base in ("mlstm", "slstm"):
        mix = (xlstm_mod.mlstm_specs if base == "mlstm"
               else xlstm_mod.slstm_specs)(_xlstm_cfg(cfg))
        sp = {"ln1": rmsnorm_specs(d, dt), "mix": mix}
        if cfg.d_ff:  # xlstm-1.3b has d_ff == 0: mixer-only blocks
            sp["ln2"] = rmsnorm_specs(d, dt)
            sp["mlp"] = mlp_specs(d, cfg.d_ff, dt, cfg.act)
        return sp
    if base == "cross":
        return {
            "ln1": rmsnorm_specs(d, dt),
            "attn": attn.cross_attn_specs(_attn_cfg(cfg, kind)),
            "ln2": rmsnorm_specs(d, dt),
            "mlp": mlp_specs(d, cfg.d_ff, dt, cfg.act),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def param_specs(cfg: ModelConfig) -> Pytree:
    sp: dict = {}
    if cfg.input_mode == "tokens":
        sp["embed"] = embed_specs(cfg.vocab_size, cfg.d_model, cfg.jdtype)
    sp["prefix"] = [block_specs(cfg, k) for k in cfg.prefix_pattern]
    S = cfg.num_superblocks
    sp["blocks"] = {
        f"b{i}": stack_specs(block_specs(cfg, k), S)
        for i, k in enumerate(cfg.pattern)
        if not k.startswith("shared_attn")
    }
    if any(k.startswith("shared_attn") for k in cfg.pattern):
        sp["shared"] = block_specs(cfg, "shared_attn")
    sp["final_norm"] = rmsnorm_specs(cfg.d_model, cfg.jdtype)
    if not cfg.tie_embeddings:
        sp["lm_head"] = lm_head_specs(cfg.vocab_size, cfg.d_model, cfg.jdtype)
    return sp


def init_params(cfg: ModelConfig, key: jax.Array) -> Pytree:
    return init_from_specs(param_specs(cfg), key)


# ---------------------------------------------------------------------------
# block application (training / full-sequence mode)
# ---------------------------------------------------------------------------

def apply_block(cfg: ModelConfig, kind: str, p: Pytree, x: jnp.ndarray,
                positions: jnp.ndarray, enc: jnp.ndarray | None) -> jnp.ndarray:
    base = kind.split(":")[0]
    ac = _attn_cfg(cfg, kind)
    if base in ("dense", "shared_attn", "moe"):
        h = rmsnorm(p["ln1"], x)
        if ac.is_mla:
            h = attn.mla_forward(p["attn"], ac, h, positions)
        else:
            h = attn.gqa_forward(p["attn"], ac, h, positions)
        x = x + h
        h = rmsnorm(p["ln2"], x)
        if base == "moe":
            h = moe_mod.moe_apply(p["moe"], _moe_cfg(cfg), h)
        else:
            h = mlp(p["mlp"], h, cfg.act, dense_mode=cfg.dense_kernel)
        return x + h
    if base == "mamba":
        return x + ssm_mod.ssm_forward(p["ssm"], _ssm_cfg(cfg), rmsnorm(p["ln"], x))
    if base in ("mlstm", "slstm"):
        fwd = xlstm_mod.mlstm_forward if base == "mlstm" else xlstm_mod.slstm_forward
        x = x + fwd(p["mix"], _xlstm_cfg(cfg), rmsnorm(p["ln1"], x))
        if cfg.d_ff:
            x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.act, dense_mode=cfg.dense_kernel)
        return x
    if base == "cross":
        h = attn.cross_attn_forward(p["attn"], ac, rmsnorm(p["ln1"], x), enc)
        x = x + h
        return x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.act, dense_mode=cfg.dense_kernel)
    raise ValueError(kind)


def _superblock_apply(cfg: ModelConfig, shared: Pytree | None, enc):
    """Returns apply_fn(carry, stacked_ws_for_one_superblock) for scan/stream."""

    def apply_fn(carry, ws):
        x, positions = carry
        for i, kind in enumerate(cfg.pattern):
            if kind.startswith("shared_attn"):
                x = apply_block(cfg, kind, shared, x, positions, enc)
            else:
                x = apply_block(cfg, kind, ws[f"b{i}"], x, positions, enc)
        return (x, positions), None

    return apply_fn


def _wsc(x, pspec, mesh):
    """with_sharding_constraint that tolerates mesh-less runs."""
    if mesh is None or pspec is None or getattr(mesh, "empty", False):
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))


def forward(params: Pytree, cfg: ModelConfig, batch: dict,
            mesh=None, shard_specs=None, full_specs=None,
            return_hidden: bool = False, act_pspec=None) -> jnp.ndarray:
    """Full-sequence forward to logits.  batch keys: tokens|embeds, [enc]."""
    if cfg.input_mode == "tokens":
        x = _embed_tokens(params, cfg, batch["tokens"])
    else:
        x = batch["embeds"].astype(cfg.jdtype)
    # pin activation layout (batch over dp axes) — XLA otherwise may unshard
    # the batch and blow up attention temp memory
    x = _wsc(x, act_pspec, mesh)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    enc = batch.get("enc")
    if enc is not None:
        enc = enc.astype(cfg.jdtype)

    for kind, p in zip(cfg.prefix_pattern, params["prefix"]):
        x = apply_block(cfg, kind, p, x, positions, enc)

    shared = params.get("shared")
    apply_fn = _superblock_apply(cfg, shared, enc)

    if cfg.stream.mode == "resident" or shard_specs is None:
        def body(carry, ws):
            return apply_fn(carry, ws)
        if cfg.remat == "block":
            body = jax.checkpoint(body)
        (x, _), _ = jax.lax.scan(body, (x, positions), params["blocks"])
    else:
        def stream_apply(carry, ws):
            new_carry, _ = apply_fn(carry, ws)
            return new_carry
        if cfg.remat == "block":
            stream_apply = jax.checkpoint(stream_apply)
        x, _ = stream_layers(
            stream_apply, (x, positions), params["blocks"], cfg.num_superblocks,
            settings=cfg.stream, mesh=mesh,
            shard_specs=shard_specs, full_specs=full_specs,
        )

    if return_hidden:
        return rmsnorm(params["final_norm"], x)
    return _logits_head(params, cfg, x)


def hidden_states(params: Pytree, cfg: ModelConfig, batch: dict,
                  mesh=None, shard_specs=None, full_specs=None,
                  act_pspec=None) -> jnp.ndarray:
    """Forward up to (and including) the final norm — no LM head."""
    return forward(params, cfg, batch, mesh, shard_specs, full_specs,
                   return_hidden=True, act_pspec=act_pspec)


def loss_fn(params: Pytree, cfg: ModelConfig, batch: dict,
            mesh=None, shard_specs=None, full_specs=None,
            act_pspec=None) -> jnp.ndarray:
    x = hidden_states(params, cfg, batch, mesh, shard_specs, full_specs,
                      act_pspec=act_pspec)

    if cfg.tie_embeddings:
        head = lambda xc: unembed(params["embed"], xc)
    else:
        head = lambda xc: lm_head(params["lm_head"], xc)
    return cross_entropy_chunked(head, x, batch["labels"], chunk=512)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _block_cache_specs(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    base = kind.split(":")[0]
    if base in ("dense", "shared_attn", "moe"):
        return attn.cache_specs(_attn_cfg(cfg, kind), batch, max_len)
    if base == "mamba":
        return ssm_mod.ssm_state_specs(_ssm_cfg(cfg), batch)
    if base == "mlstm":
        return xlstm_mod.mlstm_state_specs(_xlstm_cfg(cfg), batch)
    if base == "slstm":
        return xlstm_mod.slstm_state_specs(_xlstm_cfg(cfg), batch)
    if base == "cross":
        return None  # K/V come from the static encoder embeddings
    raise ValueError(kind)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Pytree:
    S = cfg.num_superblocks
    caches = {
        "prefix": [
            _block_cache_specs(cfg, k, batch, max_len) for k in cfg.prefix_pattern
        ],
        "blocks": {},
    }
    for i, k in enumerate(cfg.pattern):
        cs = _block_cache_specs(cfg, k, batch, max_len)
        if cs is not None:
            caches["blocks"][f"b{i}"] = jax.tree.map(
                lambda s: sds((S, *s.shape), s.dtype), cs
            )
    return caches


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def _block_prefill(cfg, kind, p, x, positions, enc, max_len):
    base = kind.split(":")[0]
    ac = _attn_cfg(cfg, kind)
    if base in ("dense", "shared_attn", "moe"):
        h = rmsnorm(p["ln1"], x)
        if ac.is_mla:
            h, cache = attn.mla_prefill(p["attn"], ac, h, positions, max_len)
        else:
            h, cache = attn.gqa_prefill(p["attn"], ac, h, positions, max_len)
        x = x + h
        h = rmsnorm(p["ln2"], x)
        if base == "moe":
            h = moe_mod.moe_apply(p["moe"], _moe_cfg(cfg), h)
        else:
            h = mlp(p["mlp"], h, cfg.act, dense_mode=cfg.dense_kernel)
        return x + h, cache
    if base == "mamba":
        y, st = ssm_mod.ssm_prefill(p["ssm"], _ssm_cfg(cfg), rmsnorm(p["ln"], x))
        return x + y, st
    if base in ("mlstm", "slstm"):
        fn = xlstm_mod.mlstm_prefill if base == "mlstm" else xlstm_mod.slstm_prefill
        y, st = fn(p["mix"], _xlstm_cfg(cfg), rmsnorm(p["ln1"], x))
        x = x + y
        if cfg.d_ff:
            x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.act, dense_mode=cfg.dense_kernel)
        return x, st
    if base == "cross":
        return apply_block(cfg, kind, p, x, positions, enc), None
    raise ValueError(kind)


def _block_decode(cfg, kind, p, x, cache, pos, enc):
    base = kind.split(":")[0]
    ac = _attn_cfg(cfg, kind)
    if base in ("dense", "shared_attn", "moe"):
        h = rmsnorm(p["ln1"], x)
        if ac.is_mla:
            h, cache = attn.mla_decode(p["attn"], ac, h, cache, pos)
        else:
            h, cache = attn.gqa_decode(p["attn"], ac, h, cache, pos)
        x = x + h
        h = rmsnorm(p["ln2"], x)
        if base == "moe":
            h = moe_mod.moe_apply(p["moe"], _moe_cfg(cfg), h)
        else:
            h = mlp(p["mlp"], h, cfg.act, dense_mode=cfg.dense_kernel)
        return x + h, cache
    if base == "mamba":
        y, st = ssm_mod.ssm_decode(p["ssm"], _ssm_cfg(cfg), rmsnorm(p["ln"], x), cache)
        return x + y, st
    if base in ("mlstm", "slstm"):
        fn = xlstm_mod.mlstm_decode if base == "mlstm" else xlstm_mod.slstm_decode
        y, st = fn(p["mix"], _xlstm_cfg(cfg), rmsnorm(p["ln1"], x), cache)
        x = x + y
        if cfg.d_ff:
            x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.act, dense_mode=cfg.dense_kernel)
        return x, st
    if base == "cross":
        positions = None
        h = attn.cross_attn_forward(p["attn"], ac, rmsnorm(p["ln1"], x), enc)
        x = x + h
        return x + mlp(p["mlp"], rmsnorm(p["ln2"], x), cfg.act, dense_mode=cfg.dense_kernel), None
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# paged prefill / decode (serving subsystem)
# ---------------------------------------------------------------------------
#
# The paged path serves attention-cache architectures (dense / shared_attn /
# moe blocks, incl. window + MLA variants).  Recurrent blocks (mamba /
# mlstm / slstm) carry O(1) state rather than per-token KV, and chunked
# prefill of a *padded* prompt would push pad tokens through their state
# update — they stay on the dense-cache engine (ROADMAP open item: masked
# state updates would lift this).

PAGED_BLOCK_KINDS = ("dense", "shared_attn", "moe")


def is_stacked_cache_path(path) -> bool:
    """True for cache-pytree leaves under the stacked "blocks" group, whose
    leading dim is the superblock stack (so the lane/pool dim sits at axis 1,
    not 0).  Single source of truth for every consumer that needs the
    batch/pool axis of a cache leaf — the layout is defined by `cache_specs`
    / `paged_cache_specs` in this module."""
    return any(getattr(k, "key", None) == "blocks" for k in path)


def supports_paged(cfg: ModelConfig) -> bool:
    kinds = tuple(cfg.prefix_pattern) + tuple(cfg.pattern)
    return (cfg.input_mode == "tokens"
            and all(k.split(":")[0] in PAGED_BLOCK_KINDS for k in kinds))


def layer_reach(cfg: ModelConfig, kind: str) -> str:
    """Attention reach class of a block kind: "window" for sliding-window
    layers (bounded lookback), "global" otherwise — the bucketing key for
    per-layer-group block tables."""
    return "window" if (kind.endswith(":window") and cfg.window_size) \
        else "global"


def layer_group_keys(cfg: ModelConfig) -> "tuple[str, ...]":
    """Distinct attention-reach classes across every layer, in first-
    appearance order.  Each class gets its OWN paged block table + pool id
    space (`serving.cache.GroupedPagedCache`), so sliding-window layers
    reclaim expired blocks even when global layers in the same model pin
    full history — gemma3's 5-local:1-global stack plateaus on the local
    group while only the global group grows."""
    kinds = tuple(cfg.prefix_pattern) + tuple(cfg.pattern)
    keys: "list[str]" = []
    for k in kinds:
        r = layer_reach(cfg, k)
        if r not in keys:
            keys.append(r)
    return tuple(keys) or ("global",)


def layer_group_index(cfg: ModelConfig, kind: str) -> int:
    return layer_group_keys(cfg).index(layer_reach(cfg, kind))


def group_horizons(cfg: ModelConfig) -> "tuple[int | None, ...]":
    """Per layer group: the oldest position its layers can still attend to,
    relative to the current query (None = unbounded).  A group's blocks
    wholly behind its horizon are reclaimable — per group, unlike
    `window_horizon`, which is the whole-model (shared-table) condition."""
    return tuple(cfg.window_size if k == "window" else None
                 for k in layer_group_keys(cfg))


def cache_path_group(cfg: ModelConfig, path) -> int:
    """Layer-group index of a paged-cache pytree leaf, from its tree path
    (the layout defined by `paged_cache_specs`): "prefix"/i leaves follow
    prefix_pattern[i], "blocks"/"b{i}" leaves follow pattern[i].  Engine-
    side pool permutes (defragment) and COW block copies use this to apply
    each group's remap to exactly its own layers' pools."""
    for i, k in enumerate(path):
        key = getattr(k, "key", None)
        if key == "prefix":
            return layer_group_index(cfg, cfg.prefix_pattern[path[i + 1].idx])
        if key == "blocks":
            return layer_group_index(cfg, cfg.pattern[int(path[i + 1].key[1:])])
    raise ValueError(f"not a paged-cache leaf path: {path}")


def _group_table(cfg: ModelConfig, kind: str, tables):
    """Resolve a layer's block table from per-group tables (tuple/list, one
    per `layer_group_keys` entry) or a single shared table (back-compat:
    models whose layers all share one reach)."""
    if isinstance(tables, (tuple, list)):
        return tables[layer_group_index(cfg, kind)]
    return tables


def window_horizon(cfg: ModelConfig) -> "int | None":
    """Oldest position any layer can still attend to, relative to the
    current query position — the block-reclamation horizon.

    Finite only when EVERY layer is sliding-window: block tables are shared
    across layers, so a physical block is reclaimable only once every
    layer's mask has moved past it.  One full-attention (or MLA) layer pins
    the whole history -> None (no reclamation), which is why gemma3's global
    layers keep their full-length KV while an all-local stack plateaus.
    """
    kinds = tuple(cfg.prefix_pattern) + tuple(cfg.pattern)
    if not kinds:
        return None
    for k in kinds:
        if not k.endswith(":window"):
            return None
    return cfg.window_size


def paged_cache_specs(cfg: ModelConfig, num_blocks: int, block_size: int) -> Pytree:
    """Pool ShapeDtypeStructs mirroring `cache_specs`' tree structure, with
    the per-lane (batch, max_len) dims replaced by shared
    (num_blocks, block_size) pools.  Stacked superblock leaves keep their
    leading S dim; physical block ids index the second axis there."""
    if not supports_paged(cfg):
        bad = [k for k in tuple(cfg.prefix_pattern) + tuple(cfg.pattern)
               if k.split(":")[0] not in PAGED_BLOCK_KINDS]
        raise ValueError(
            f"{cfg.name}: paged KV serves attention-cache blocks only; "
            f"unsupported kinds {bad} (use the dense-cache engine)")
    S = cfg.num_superblocks
    caches = {
        "prefix": [
            attn.paged_cache_specs(_attn_cfg(cfg, k), num_blocks, block_size)
            for k in cfg.prefix_pattern
        ],
        "blocks": {},
    }
    for i, k in enumerate(cfg.pattern):
        cs = attn.paged_cache_specs(_attn_cfg(cfg, k), num_blocks, block_size)
        caches["blocks"][f"b{i}"] = jax.tree.map(
            lambda s: sds((S, *s.shape), s.dtype), cs)
    return caches


def _ffn_paged(cfg, base, p, x):
    """The feed-forward half of a served block.  An MoE layer drops no
    token here: its capacity holds every token of a group, so what a token
    gets is its own experts' output, whatever its batch-mates route to."""
    h = rmsnorm(p["ln2"], x)
    if base == "moe":
        return moe_mod.moe_apply(p["moe"], _moe_cfg(cfg, dropless=True), h)
    return mlp(p["mlp"], h, cfg.act, dense_mode=cfg.dense_kernel)


def served_moe_rows(cfg: ModelConfig, rows: int, real: int
                    ) -> "tuple[int, int]":
    """(rows routed, rows computed) of the routed-expert matmuls of one
    served step program over `rows` tokens, `real` of them real, summed
    over the MoE layers: real x experts per token, and experts x groups x
    capacity (`moe.expert_rows`).  (0, 0) for a model without MoE layers."""
    layers = (sum(k.startswith("moe") for k in cfg.prefix_pattern)
              + sum(k.startswith("moe") for k in cfg.pattern)
              * cfg.num_superblocks)
    if not layers:
        return 0, 0
    c = _moe_cfg(cfg, dropless=True)
    return (layers * c.experts_per_token * real,
            layers * moe_mod.expert_rows(c, rows))


# attention's projections a served step streams through `dense`, with the
# weight dims each contracts (MLA's w_uk and w_uv are folded into einsums
# on the kernel read path)
_SERVED_PROJECTIONS = {"w_q": 1, "w_k": 1, "w_v": 1, "w_dq": 1, "w_uq": 1,
                       "w_dkv": 1, "w_o": 2}


def _served_matmuls(cfg: ModelConfig, kind: str, rows: int):
    """(rows, weight, x dtype) of each `dense` / `dense_grouped` call of
    one served block of `kind` over `rows` tokens, the weight as the call
    sees it: a (K, N) or, grouped, an (E, K, N) ShapeDtypeStruct."""
    sp = block_specs(cfg, kind)
    dt = cfg.jdtype
    calls = []
    for name, w in sp["attn"].items():
        c = _SERVED_PROJECTIONS.get(name)
        if c:
            calls.append((rows, sds((math.prod(w.shape[:c]),
                                     math.prod(w.shape[c:])), w.dtype), dt))
    calls += [(rows, w, dt) for w in sp.get("mlp", {}).values()]
    if "moe" in sp:
        m, c = sp["moe"], _moe_cfg(cfg, dropless=True)
        names = (("w_gate", "w_up", "w_down") if c.act == "swiglu"
                 else ("w_up", "w_down"))
        calls.append((rows, sds(m["router"].shape, c.router_dtype),
                      c.router_dtype))
        per_expert = moe_mod.expert_rows(c, rows) // c.num_experts
        calls += [(per_expert, m[k], dt) for k in names]
        calls += [(rows, m["shared"][k], dt) for k in names
                  if k in m.get("shared", {})]
    return calls


def served_gpp_pad_bytes(cfg: ModelConfig, rows: int) -> int:
    """Weight bytes one served step program over `rows` tokens copies into
    zero-padded `gpp_matmul` / `gpp_matmul_grouped` operands, summed over
    its layers: each call `dense` routes to a kernel, padded as the
    wrappers pad it (`gpp_matmul.weight_pad_bytes`).  0 when every weight
    is routed to the reference or every planned tile divides its weight."""
    total = 0
    kinds = ([(k, 1) for k in cfg.prefix_pattern]
             + [(k, cfg.num_superblocks) for k in cfg.pattern])
    for kind, count in kinds:
        for M, w, x_dtype in _served_matmuls(cfg, kind, rows):
            mode = resolve_dense_mode(cfg.dense_kernel, w)
            if mode == "ref":
                continue
            total += count * weight_pad_bytes(
                M, *w.shape[-2:], x_dtype, w.dtype,
                experts=w.shape[0] if w.ndim == 3 else 1,
                chip=target_chip(mode == "interpret"))
    return total


def _block_prefill_paged(cfg, kind, p, x, cache, table_row, start_pos):
    ac = _attn_cfg(cfg, kind)
    base = kind.split(":")[0]
    row = _group_table(cfg, kind, table_row)
    h = rmsnorm(p["ln1"], x)
    if ac.is_mla:
        h, cache = attn.mla_prefill_paged(p["attn"], ac, h, cache, row,
                                          start_pos)
    else:
        h, cache = attn.gqa_prefill_paged(p["attn"], ac, h, cache, row,
                                          start_pos)
    x = x + h
    return x + _ffn_paged(cfg, base, p, x), cache


def _block_decode_paged(cfg, kind, p, x, cache, tables, positions, active):
    ac = _attn_cfg(cfg, kind)
    base = kind.split(":")[0]
    tb = _group_table(cfg, kind, tables)
    h = rmsnorm(p["ln1"], x)
    if ac.is_mla:
        h, cache = attn.mla_decode_paged(p["attn"], ac, h, cache, tb,
                                         positions, active)
    else:
        h, cache = attn.gqa_decode_paged(p["attn"], ac, h, cache, tb,
                                         positions, active)
    x = x + h
    return x + _ffn_paged(cfg, base, p, x), cache


def _block_verify_paged(cfg, kind, p, x, cache, tables, positions, active,
                        nvalid):
    ac = _attn_cfg(cfg, kind)
    base = kind.split(":")[0]
    tb = _group_table(cfg, kind, tables)
    h = rmsnorm(p["ln1"], x)
    if ac.is_mla:
        h, cache = attn.mla_verify_paged(p["attn"], ac, h, cache, tb,
                                         positions, active, nvalid)
    else:
        h, cache = attn.gqa_verify_paged(p["attn"], ac, h, cache, tb,
                                         positions, active, nvalid)
    x = x + h
    return x + _ffn_paged(cfg, base, p, x), cache


def _embed_tokens(params, cfg: ModelConfig, tokens):
    x = embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _logits_head(params, cfg: ModelConfig, x):
    x = rmsnorm(params["final_norm"], x)
    return (unembed(params["embed"], x) if cfg.tie_embeddings
            else lm_head(params["lm_head"], x))


def prefill_chunk(params: Pytree, cfg: ModelConfig, tokens, caches,
                  table_row, start_pos, last_idx):
    """Process one block-aligned prompt chunk for a single lane.

    tokens: (1, chunk) — the chunk's token ids (pads beyond the real prompt
      are harmless: their pool slots are overwritten by decode writes at the
      same absolute positions, and the causal mask hides them until then).
    table_row: the lane's block table(s) — a (1, max_blocks) array, or a
      tuple of one such array per layer group (`layer_group_keys`) when
      window and global layers keep separate tables.
    start_pos: traced scalar — absolute position of tokens[0].  ANY token
      index: with a prefix-cache hit the first chunk starts at the matched
      token count, mid-block when a shared tail block was forked; the paged
      KV write scatters per token and the read masks are position-exact, so
      no alignment is required.
    last_idx: traced scalar — chunk-local index whose logits the engine
      samples from (the prompt's true last token on the final chunk; ignored
      on earlier chunks).

    Returns (logits (1, vocab), caches).  The chunk size is the ONLY shape
    this function is compiled for — the generalized-ping-pong move applied
    to prefill: a bursty whole-prompt rewrite becomes fixed-size chunks
    interleaved with decode steps, so per-step token count (and HBM traffic)
    stays flat and jit shapes are bounded.
    """
    x = _embed_tokens(params, cfg, tokens)
    new_prefix = []
    for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
        x, c = _block_prefill_paged(cfg, kind, p, x, c, table_row, start_pos)
        new_prefix.append(c)

    shared = params.get("shared")

    def body(carry, xs):
        x = carry
        ws, cache = xs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            p = shared if kind.startswith("shared_attn") else ws[f"b{i}"]
            x, c_out = _block_prefill_paged(cfg, kind, p, x, cache[f"b{i}"],
                                            table_row, start_pos)
            new_caches[f"b{i}"] = c_out
        return x, new_caches

    x, blk_caches = jax.lax.scan(body, x, (params["blocks"], caches["blocks"]))
    x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    logits = _logits_head(params, cfg, x_last)
    return logits[:, 0], {"prefix": new_prefix, "blocks": blk_caches}


def decode_step_paged(params: Pytree, cfg: ModelConfig, tokens, caches,
                      tables, positions, active):
    """One batched decode step over the paged pools.

    tokens: (slots, 1); tables: (slots, max_blocks) — or a tuple of one
    such array per layer group (`layer_group_keys`); positions: (slots,) —
    PER-LANE absolute positions, so heterogeneous lanes decode in ONE call
    (the seed engine ran one call per distinct position); active: (slots,)
    bool — inactive lanes write to the null block and their logits are
    garbage the engine ignores.

    Returns (logits (slots, 1, vocab), caches).
    """
    x = _embed_tokens(params, cfg, tokens)
    new_prefix = []
    for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
        x, c = _block_decode_paged(cfg, kind, p, x, c, tables, positions, active)
        new_prefix.append(c)

    shared = params.get("shared")

    def body(carry, xs):
        x = carry
        ws, cache = xs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            p = shared if kind.startswith("shared_attn") else ws[f"b{i}"]
            x, c_out = _block_decode_paged(cfg, kind, p, x, cache[f"b{i}"],
                                           tables, positions, active)
            new_caches[f"b{i}"] = c_out
        return x, new_caches

    x, blk_caches = jax.lax.scan(body, x, (params["blocks"], caches["blocks"]))
    logits = _logits_head(params, cfg, x)
    return logits, {"prefix": new_prefix, "blocks": blk_caches}


def verify_step_paged(params: Pytree, cfg: ModelConfig, tokens, caches,
                      tables, positions, active, nvalid):
    """Batched speculative-verify step: score S = draft_len+1 tokens per
    lane in ONE forward pass over the paged pools — the GPP amortization
    move for decode, where the streamed weight working set otherwise buys
    a single token per lane.

    tokens: (slots, S) — row = [last produced token, draft_1..draft_k,
      pads]; tables/positions/active as in `decode_step_paged` (positions
      are per-lane START positions — query row s sits at positions[b]+s);
    nvalid: (slots,) int32 — real tokens per lane (1 + its draft length).
      Rows past nvalid write null block 0 and yield garbage logits the
      engine never reads, so this ONE (slots, S) shape serves every
      draft-length / acceptance pattern — the third and final jitted step
      shape next to prefill_chunk and decode_step_paged.

    Returns (logits (slots, S, vocab), caches): logits[b, i] scores the
    token AFTER tokens[b, i], exactly what acceptance sampling compares
    against draft_{i+1}.
    """
    x = _embed_tokens(params, cfg, tokens)
    new_prefix = []
    for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
        x, c = _block_verify_paged(cfg, kind, p, x, c, tables, positions,
                                   active, nvalid)
        new_prefix.append(c)

    shared = params.get("shared")

    def body(carry, xs):
        x = carry
        ws, cache = xs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            p = shared if kind.startswith("shared_attn") else ws[f"b{i}"]
            x, c_out = _block_verify_paged(cfg, kind, p, x, cache[f"b{i}"],
                                           tables, positions, active, nvalid)
            new_caches[f"b{i}"] = c_out
        return x, new_caches

    x, blk_caches = jax.lax.scan(body, x, (params["blocks"], caches["blocks"]))
    logits = _logits_head(params, cfg, x)
    return logits, {"prefix": new_prefix, "blocks": blk_caches}


def prefill(params: Pytree, cfg: ModelConfig, batch: dict, max_len: int,
            mesh=None, act_pspec=None):
    """Process the prompt; returns (last-position logits, caches)."""
    if cfg.input_mode == "tokens":
        x = _embed_tokens(params, cfg, batch["tokens"])
    else:
        x = batch["embeds"].astype(cfg.jdtype)
    x = _wsc(x, act_pspec, mesh)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    enc = batch.get("enc")
    if enc is not None:
        enc = enc.astype(cfg.jdtype)

    caches = {"prefix": [], "blocks": {}}
    for kind, p in zip(cfg.prefix_pattern, params["prefix"]):
        x, c = _block_prefill(cfg, kind, p, x, positions, enc, max_len)
        caches["prefix"].append(c)

    shared = params.get("shared")

    def body(carry, ws):
        x = carry
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            p = shared if kind.startswith("shared_attn") else ws[f"b{i}"]
            x, c = _block_prefill(cfg, kind, p, x, positions, enc, max_len)
            if c is not None:
                new_caches[f"b{i}"] = c
        return x, new_caches

    x, blk_caches = jax.lax.scan(body, x, params["blocks"])
    caches["blocks"] = blk_caches

    logits = _logits_head(params, cfg, x[:, -1:])
    return logits, caches


def decode_step(params: Pytree, cfg: ModelConfig, tokens_or_embeds, caches, pos,
                enc=None):
    """One decode step.  tokens: (B, 1) ints (or (B,1,D) embeds).  pos: traced
    scalar — absolute position of the new token."""
    if cfg.input_mode == "tokens":
        x = _embed_tokens(params, cfg, tokens_or_embeds)
    else:
        x = tokens_or_embeds.astype(cfg.jdtype)
    if enc is not None:
        enc = enc.astype(cfg.jdtype)

    new_prefix = []
    for kind, p, c in zip(cfg.prefix_pattern, params["prefix"], caches["prefix"]):
        x, c = _block_decode(cfg, kind, p, x, c, pos, enc)
        new_prefix.append(c)

    shared = params.get("shared")

    def body(carry, xs):
        x = carry
        ws, cache = xs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            p = shared if kind.startswith("shared_attn") else ws[f"b{i}"]
            c_in = cache.get(f"b{i}")
            x, c_out = _block_decode(cfg, kind, p, x, c_in, pos, enc)
            if c_out is not None:
                new_caches[f"b{i}"] = c_out
        return x, new_caches

    x, blk_caches = jax.lax.scan(body, x, (params["blocks"], caches["blocks"]))

    logits = _logits_head(params, cfg, x)
    return logits, {"prefix": new_prefix, "blocks": blk_caches}
