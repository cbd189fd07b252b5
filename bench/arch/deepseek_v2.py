"""The DeepSeek-V2 architecture (`model_type` "deepseek_v2"), as the harness
needs it: the program's `ModelConfig` from a published config.json, the
config at a width the CPU tests can run, the weight leaves the timed path
streams through the GPP kernels, and the work of one serving step.  The
names are those `bench/arch/qwen2.py` lists; the step dicts also carry
`grouped` (`Work` of every `gpp_matmul_grouped` call) and `grouped_calls`.

A layer is latent attention (MLA, without q compression) and then either
a dense SwiGLU MLP (the first `first_k_dense_replace` layers) or routed
experts (softmax over `n_routed_experts`, top `num_experts_per_tok`) with
`n_shared_experts` shared ones.  On the timed path:
- `gpp_matmul` takes w_q, w_dkv and w_o of every layer, the dense MLP and
  the shared experts: 6 calls a layer.  The router (d x experts, f32) is
  under the kernel's 1 MiB floor and is a plain matmul; w_uk and w_uv are
  absorbed into the query and the output around the attention kernel.
- `gpp_matmul_grouped` takes the routed experts' gate, up and down: 3
  calls an MoE layer.  Each streams every expert's weights; its rows are
  experts x groups x capacity, and serving's dropless capacity makes that
  the call's tokens for every expert.  Its least time counts the bytes of
  all experts and the FLOPs of the routed rows alone (tokens x experts per
  token), so rows that carry no token show as lost roofline.
- `paged_attention` (mla=True) reads one latent row a token: kv_lora_rank
  + qk_rope_head_dim lanes, once, for all heads.
"""
from __future__ import annotations

import dataclasses

from bench.work import Work, gpp_call

# q, the latent down-projection and o of attention; gate, up and down of
# the routed experts (grouped) and of the shared experts (plain)
KERNEL_WEIGHTS = frozenset({"w_q", "w_dkv", "w_o", "w_gate", "w_up",
                            "w_down"})


def model_config(name: str, hf: dict):
    """The program's ModelConfig for a DeepSeek-V2 config.json.  Refuses
    what the program or the reference does not compute."""
    from repro.configs.base import ModelConfig, YarnScaling
    scaling = hf.get("rope_scaling")
    refused = {
        "model_type": hf["model_type"] != "deepseek_v2",
        "n_group": hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1,
        "scoring_func": hf.get("scoring_func", "softmax") != "softmax",
        "topk_method": hf.get("topk_method", "greedy") != "greedy",
        "q_lora_rank": hf.get("q_lora_rank") is not None,
        "v_head_dim": hf["v_head_dim"] != hf["qk_nope_head_dim"],
        "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
        "attention_bias": bool(hf.get("attention_bias")),
        "hidden_act": hf.get("hidden_act", "silu") != "silu",
        "rms_norm_eps": hf["rms_norm_eps"] != 1e-6,
        "rope_scaling": scaling is not None and scaling.get("type") != "yarn",
        # the program does not scale the routed experts' sum
        "routed_scaling_factor": hf.get("routed_scaling_factor", 1) != 1,
    }
    bad = sorted(k for k, v in refused.items() if v)
    if bad:
        raise ValueError(f"{name}: the program does not map the "
                         f"deepseek_v2 settings {bad}")
    yarn = None if scaling is None else YarnScaling(
        factor=float(scaling["factor"]),
        original_max_position_embeddings=int(
            scaling["original_max_position_embeddings"]),
        beta_fast=float(scaling.get("beta_fast", 32)),
        beta_slow=float(scaling.get("beta_slow", 1)),
        mscale=float(scaling.get("mscale", 1)),
        mscale_all_dim=float(scaling.get("mscale_all_dim", 0)))
    return ModelConfig(
        name=name, family="moe",
        d_model=hf["hidden_size"], num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_attention_heads"],
        head_dim=hf["qk_nope_head_dim"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], pattern=("moe",),
        prefix_pattern=("dense",) * hf["first_k_dense_replace"],
        kv_lora_rank=hf["kv_lora_rank"], q_lora_rank=None,
        rope_head_dim=hf["qk_rope_head_dim"],
        num_experts=hf["n_routed_experts"],
        experts_per_token=hf["num_experts_per_tok"],
        num_shared_experts=hf["n_shared_experts"],
        moe_d_ff=hf["moe_intermediate_size"],
        moe_norm_topk_prob=bool(hf["norm_topk_prob"]),
        rope_theta=float(hf["rope_theta"]), rope_scaling=yarn,
        rope_interleave=True, norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"], dtype=hf["torch_dtype"])


def smoke_hf(hf: dict) -> dict:
    """The config at a width the CPU tests can run, with the architecture
    kept: MLA with a rope part, a dense layer and then two MoE layers, 8
    routed experts of which each token takes 2, the shared experts, YaRN."""
    return dict(hf, hidden_size=128, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                kv_lora_rank=64, qk_nope_head_dim=32, v_head_dim=32,
                qk_rope_head_dim=16, intermediate_size=256,
                moe_intermediate_size=64, n_routed_experts=8,
                num_experts_per_tok=2, vocab_size=512)


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a DeepSeek-V2 model that fix its work."""
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    kv_lora: int
    dense_ff: int
    moe_ff: int
    experts: int
    top_k: int
    shared: int
    dense_layers: int
    layers: int
    vocab: int
    tied: bool
    itemsize: int = 2

    @classmethod
    def from_hf(cls, hf: dict) -> "Shape":
        return cls(d=hf["hidden_size"], heads=hf["num_attention_heads"],
                   nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                   v=hf["v_head_dim"], kv_lora=hf["kv_lora_rank"],
                   dense_ff=hf["intermediate_size"],
                   moe_ff=hf["moe_intermediate_size"],
                   experts=hf["n_routed_experts"],
                   top_k=hf["num_experts_per_tok"],
                   shared=hf["n_shared_experts"],
                   dense_layers=hf["first_k_dense_replace"],
                   layers=hf["num_hidden_layers"], vocab=hf["vocab_size"],
                   tied=hf["tie_word_embeddings"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    def attn_projections(self) -> "list[tuple[int, int]]":
        """(K, N) of the gpp_matmul projections of attention: q, the latent
        down-projection (with the rope part) and o."""
        H = self.heads
        return [(self.d, H * (self.nope + self.rope)),
                (self.d, self.kv_lora + self.rope), (H * self.v, self.d)]

    def mlp_projections(self, f: int) -> "list[tuple[int, int]]":
        """(K, N) of a SwiGLU MLP of width f: gate, up, down."""
        return [(self.d, f), (self.d, f), (f, self.d)]

    def attn_params(self) -> int:
        """Attention's parameters a token activates: the projections and
        the latent up-projections w_uk and w_uv."""
        absorbed = self.kv_lora * self.heads * (self.nope + self.v)
        return sum(k * n for k, n in self.attn_projections()) + absorbed

    def expert_params(self) -> int:
        return 3 * self.d * self.moe_ff

    def activated_params(self) -> int:
        """Parameters one token activates: every layer's attention, the
        dense MLPs, each MoE layer's router and its top_k routed and shared
        experts, and the LM head."""
        dense = self.dense_layers * 3 * self.d * self.dense_ff
        moe = self.moe_layers * (self.d * self.experts + (
            self.top_k + self.shared) * self.expert_params())
        return (self.layers * self.attn_params() + dense + moe
                + self.vocab * self.d)

    def streamed_params(self) -> int:
        """Parameters a step reads: every layer's, with all routed experts,
        and the LM head (the embedding rows a step looks up are
        negligible)."""
        return (self.activated_params() + self.moe_layers
                * (self.experts - self.top_k) * self.expert_params())

    def kv_bytes_per_token(self) -> int:
        """The latent pools' bytes a token: c_kv and k_rope, the k_rope
        pool padded to whole 128-lane tiles as the program stores it."""
        rope = -(-self.rope // 128) * 128
        return self.layers * (self.kv_lora + rope) * self.itemsize


def gpp_calls(shape: Shape, M: int) -> Work:
    """Every `gpp_matmul` call of one step program over M rows."""
    total = Work()
    for layer in range(shape.layers):
        f = (shape.dense_ff if layer < shape.dense_layers
             else shape.shared * shape.moe_ff)
        for K, N in shape.attn_projections() + shape.mlp_projections(f):
            total = total + gpp_call(M, K, N, itemsize=shape.itemsize)
    return total


def grouped_calls(shape: Shape, M: int) -> Work:
    """Every `gpp_matmul_grouped` call of one step program over M tokens:
    each streams all experts' weights and multiplies M rows an expert
    (dropless), of which M x top_k in all carry a token."""
    total = Work()
    E, it = shape.experts, shape.itemsize
    for K, N in shape.mlp_projections(shape.moe_ff):
        total = total + Work(2.0 * M * shape.top_k * K * N,
                             (E * K * N + E * M * K + E * M * N) * it)
    return Work(total.flops * shape.moe_layers, total.bytes * shape.moe_layers)


def attn_calls(shape: Shape, contexts, S: int) -> Work:
    """Every `paged_attention` call of one step program: the query rows
    0..S-1 of each lane at the end of its context (as
    `bench.work.paged_attn_call`), against one latent row a key (kv_lora
    + rope lanes, read once for all heads); QK over kv_lora + rope lanes
    and PV over kv_lora, 2 FLOPs a lane each.  q (absorbed) is read and
    the latent output written once."""
    lat = shape.kv_lora + shape.rope
    seen = sum(ctx - S + s + 1 for ctx in contexts for s in range(S))
    flops = 2.0 * shape.heads * (lat + shape.kv_lora) * seen
    qo = len(contexts) * S * shape.heads * (lat + shape.kv_lora)
    one = Work(flops, (sum(contexts) * lat + qo) * shape.itemsize)
    return Work(one.flops * shape.layers, one.bytes * shape.layers)


def model_flops(shape: Shape, tokens: int, attn: Work) -> float:
    """Model operations of a step that computes `tokens` rows: 2 per
    activated parameter per row (6 routed and 2 shared experts of an MoE
    layer, not all of them), plus attention.  Pad rows and inactive lanes
    are not work."""
    return 2.0 * shape.activated_params() * tokens + attn.flops


def _step(shape: Shape, M: int, attn: Work, flops: float) -> dict:
    return {"gpp": gpp_calls(shape, M), "attn": attn,
            "grouped": grouped_calls(shape, M),
            "gpp_calls": 6 * shape.layers, "attn_calls": shape.layers,
            "grouped_calls": 3 * shape.moe_layers, "model_flops": flops}


def decode_step(shape: Shape, slots: int, lanes) -> dict:
    """One decode call: `slots` rows through the projections (the call's
    shape), `lanes` the live context of each active lane; the inactive
    lanes attend to 1 row, the null row they touch."""
    contexts = list(lanes) + [1] * (slots - len(lanes))
    return _step(shape, slots, attn_calls(shape, contexts, 1),
                 model_flops(shape, len(lanes), attn_calls(shape, lanes, 1)))


def prefill_chunk(shape: Shape, rows: int, start: int, real: int) -> dict:
    """One prefill-chunk call: `rows` rows (the call's shape) at positions
    start.. of one lane, of which the first `real` are prompt tokens."""
    return _step(shape, rows, attn_calls(shape, [start + rows], rows),
                 model_flops(shape, real, attn_calls(shape, [start + real],
                                                     real)
                             if real else Work()))
