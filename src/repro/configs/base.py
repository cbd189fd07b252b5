"""Model/arch configuration dataclasses and the assigned input shapes."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.streamer import StreamSettings


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling, as a config.json's `rope_scaling` of type
    "yarn" gives it (DeepSeek-V2): frequencies past the
    `original_max_position_embeddings` context are interpolated by
    `factor`, blended over the dims between the `beta_fast` and `beta_slow`
    rotation counts, and cos/sin and the attention scale carry the
    `mscale` / `mscale_all_dim` terms (`models.layers.rope`,
    `models.attention`)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense|moe|ssm|hybrid|audio|vlm
    d_model: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    # layer composition
    pattern: tuple[str, ...] = ("dense",)
    prefix_pattern: tuple[str, ...] = ()
    head_dim: int | None = None
    # attention
    qkv_bias: bool = False
    rope_theta: float = 1e6
    rope_scaling: YarnScaling | None = None
    rope_interleave: bool = False    # rotate lanes (2j, 2j+1) as a pair
                                     # (DeepSeek-V2) instead of (j, j+hd/2)
    window_size: int | None = None
    # MLA
    kv_lora_rank: int | None = None
    q_lora_rank: int | None = None
    rope_head_dim: int = 64
    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int | None = None
    moe_capacity_factor: float = 1.25 # training; serving drops no token
    moe_norm_topk_prob: bool = True  # renormalise the top-k gate weights
    moe_serve_resident: bool = False # serving: experts resident, E over model
                                     # x d_ff over data (set by the serve steps)
    moe_ep_mode: str = "tp"          # tp: experts over model axis (+FSDP);
                                     # dp: experts over data x d_ff over model
                                     #     (weights fully sharded resident,
                                     #     tokens all-to-all — no FSDP gathers)
    # SSM
    ssm_state_dim: int = 0
    ssm_expansion: int = 2
    # modality
    input_mode: str = "tokens"       # tokens | embeddings (musicgen frontend stub)
    encoder_tokens: int = 0          # vlm: # patch embeddings from the stub
    # misc
    act: str = "swiglu"
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    embed_scale: bool = False        # gemma-style sqrt(d) embedding scaling
    dtype: str = "bfloat16"
    subquadratic: bool = False       # eligible for long_500k decode
    stream: StreamSettings = StreamSettings()
    dense_kernel: str = "auto"       # matmul routing (kernels.ops.dense /
                                     # dense_grouped) for EVERY projection —
                                     # MLP, attention q/k/v/o, MLA up/down,
                                     # MoE router+experts, SSM/xLSTM in/out:
                                     # auto | ref | kernel | interpret — auto
                                     # streams big weights through the GPP
                                     # Pallas kernel on TPU, jnp elsewhere
    paged_attn_kernel: str = "auto"  # paged serving READ path routing
                                     # (kernels.ops.paged_attn, used by the
                                     # *_paged attention fns): auto | pallas |
                                     # interpret | ref — "pallas" streams KV
                                     # blocks through the VMEM-ring Pallas
                                     # kernel (block tables as scalar
                                     # prefetch), "ref" gathers pools through
                                     # the tables (pre-kernel math, exact),
                                     # "auto" = pallas on TPU / ref elsewhere
    remat: str = "block"             # none | block  (activation checkpointing)
    optimizer: str = "adamw"         # adamw | adafactor (1T-scale state budget)
    # serving (paged-KV engine defaults; ServeConfig fields of the same
    # concept override per deployment)
    serve_block_size: int = 16       # tokens per paged-KV block
    serve_token_budget: int = 0      # flat per-step token target for the
                                     # chunked-prefill scheduler; 0 = auto
                                     # (slots + 2 blocks — one chunk of
                                     # prefill riding along with full decode)
    prefix_cache: bool = False       # paged serving: radix-tree shared-
                                     # prefix KV reuse (serving/prefix.py) —
                                     # admission maps previously computed
                                     # prompt-prefix blocks into the lane's
                                     # tables and prefill skips the matched
                                     # chunks; cached blocks are refcounted,
                                     # appended into via copy-on-write
                                     # fork_block, and LRU-evicted under
                                     # block pressure before any preemption.
                                     # ServeConfig.prefix_cache overrides.
    prefix_cache_blocks: int = 0     # cap on block references the prefix
                                     # index may pin per deployment (0 =
                                     # unbounded; pressure-driven eviction
                                     # applies either way).
                                     # ServeConfig.prefix_cache_blocks
                                     # overrides.
    speculation: bool = False        # paged serving: speculative decoding —
                                     # self-drafted (prompt-lookup n-gram)
                                     # or draft-model tokens are scored in
                                     # ONE batched verify pass of draft_len+1
                                     # tokens per lane, amortizing the
                                     # streamed weight working set over up
                                     # to draft_len+1 tokens instead of 1
                                     # (the GPP bytes-per-useful-token fix
                                     # for decode).  Greedy/temperature
                                     # output streams are token-for-token
                                     # identical with speculation on or off;
                                     # rejected drafts roll back via block-
                                     # table truncation.
                                     # ServeConfig.speculation overrides.
    draft_len: int = 4               # max draft tokens per lane per verify
                                     # step (k; the verify shape is
                                     # (slots, k+1)).  Actual per-step
                                     # drafts also respect the scheduler's
                                     # flatness slack
                                     # (core.schedule.plan_verify_budget)
                                     # and each lane's remaining quota.
                                     # ServeConfig.draft_len overrides.

    # ---- observability (repro.obs; near-zero overhead when off) ----
    obs: bool = False                # serving telemetry: request/kernel
                                     # trace spans (Perfetto trace-event
                                     # JSON via --trace-out), TTFT/TPOT
                                     # histograms (JSONL via --metrics-out),
                                     # and step wall times in the ledger.
                                     # Off: every instrumentation site is a
                                     # no-op (regression-gated < 5%
                                     # tokens/sec overhead when ON in
                                     # BENCH_serving.json).
                                     # ServeConfig.obs overrides.
    obs_trace_capacity: int = 65536  # trace ring-buffer capacity (events);
                                     # once full the OLDEST events drop and
                                     # the export's otherData.dropped_events
                                     # counts them.
                                     # ServeConfig.trace_capacity overrides.
    metrics_retention: int = 0       # per-step ledger rows kept in memory
                                     # (0 = unbounded, the test/bench
                                     # default).  > 0: a ring of the most
                                     # recent N rows; evicted rows fold into
                                     # BandwidthLedger.rollup so lifetime
                                     # totals stay exact while long serving
                                     # runs stop growing per step.
                                     # ServeConfig.metrics_retention
                                     # overrides.

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def num_superblocks(self) -> int:
        body = self.num_layers - len(self.prefix_pattern)
        if body % len(self.pattern):
            raise ValueError(
                f"{self.name}: {body} body layers not divisible by pattern "
                f"{self.pattern}"
            )
        return body // len(self.pattern)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- analytic parameter counts (roofline MODEL_FLOPS) ----
    def _block_params(self, kind: str) -> int:
        d, f, hd = self.d_model, self.d_ff, self.resolved_head_dim
        H, KV = self.num_heads, self.num_kv_heads
        base = kind.split(":")[0]
        n_mlp = d * f * (3 if self.act == "swiglu" else 2)
        if base in ("dense", "shared_attn", "moe"):
            if self.kv_lora_rank:
                r, rr = self.kv_lora_rank, self.rope_head_dim
                a = d * (r + rr) + r * H * hd * 2 + H * hd * d
                if self.q_lora_rank:
                    a += d * self.q_lora_rank + self.q_lora_rank * H * (hd + rr)
                else:
                    a += d * H * (hd + rr)
            else:
                a = d * H * hd + 2 * d * KV * hd + H * hd * d
            if base == "moe":
                fm = self.moe_d_ff or f
                active = self.experts_per_token * d * fm * (3 if self.act == "swiglu" else 2)
                shared = (self.num_shared_experts and
                          d * fm * self.num_shared_experts *
                          (3 if self.act == "swiglu" else 2)) or 0
                router = d * self.num_experts
                return a + active + shared + router
            return a + n_mlp
        if base == "mamba":
            di, N = self.ssm_expansion * d, self.ssm_state_dim
            return d * 2 * di + d * 2 * N + d * H + di * d
        if base in ("mlstm", "slstm"):
            if base == "mlstm":
                mix = 3 * d * H * (d // H) + 2 * d * H + H * (d // H) * d + d * d
            else:
                mix = 3 * d * d + 2 * d * H
            return mix + n_mlp
        if base == "cross":
            return d * H * hd + 2 * d * KV * hd + H * hd * d + n_mlp
        raise ValueError(kind)

    def active_params(self) -> int:
        """Active (per-token) parameter count — MoE counts top-k experts."""
        n = 0
        for k in self.prefix_pattern:
            n += self._block_params(k)
        for k in self.pattern:
            n += self._block_params(k) * self.num_superblocks if not k.startswith(
                "shared_attn") else 0
        if any(k.startswith("shared_attn") for k in self.pattern):
            n += self._block_params("shared_attn")
        if self.input_mode == "tokens":
            n += self.vocab_size * self.d_model
        if not self.tie_embeddings:
            n += self.vocab_size * self.d_model
        return n

    def total_params(self) -> int:
        """Total parameter count (MoE counts all experts)."""
        if not self.num_experts:
            return self.active_params()
        fm = self.moe_d_ff or self.d_ff
        per_layer_all = self.num_experts * self.d_model * fm * (
            3 if self.act == "swiglu" else 2)
        per_layer_active = self.experts_per_token * self.d_model * fm * (
            3 if self.act == "swiglu" else 2)
        n_moe_layers = sum(1 for k in self.pattern if k.startswith("moe")) \
            * self.num_superblocks + sum(
                1 for k in self.prefix_pattern if k.startswith("moe"))
        return self.active_params() + n_moe_layers * (per_layer_all - per_layer_active)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def cells_for(cfg: ModelConfig) -> list[str]:
    """The assigned (arch x shape) cells: long_500k only for sub-quadratic."""
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.subquadratic:
        names.append("long_500k")
    return names
