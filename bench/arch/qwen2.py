"""The Qwen2 architecture (`model_type` "qwen2"), as the harness needs it:
the program's `ModelConfig` from a published config.json, the config at a
width the CPU tests can run, the weight leaves the timed path streams
through `gpp_matmul`, and the work of one serving step.

Found by `bench.model.arch_for` from the configuration's `model_type`.
Every architecture module gives the same names:

- `model_config(name, hf)`, `smoke_hf(hf)`;
- `KERNEL_WEIGHTS`: the names of the leaves under `params["blocks"]` that
  must resolve to the kernel;
- `Shape.from_hf(hf)`: the sizes that fix the work;
- `decode_step(shape, slots, lanes)` and
  `prefill_chunk(shape, rows, start, real)`: the work of one step program,
  a dict with `gpp` and `attn` (`bench.work.Work` of every call of each
  kernel), `gpp_calls` and `attn_calls` (how many calls the program makes)
  and `model_flops` (the operations the model needs for the real tokens).
"""
from __future__ import annotations

import dataclasses

from bench.work import Work, gpp_call, paged_attn_call

# q, k, v and o of attention, gate, up and down of the MLP
KERNEL_WEIGHTS = frozenset({"w_q", "w_k", "w_v", "w_o", "w_gate", "w_up",
                            "w_down"})


def model_config(name: str, hf: dict):
    """The program's ModelConfig for a Qwen2-architecture config.json."""
    from repro.configs.base import ModelConfig
    if hf["model_type"] != "qwen2" or hf.get("use_sliding_window"):
        raise ValueError(f"{name}: only full-attention qwen2 configs are "
                         f"mapped, got {hf['model_type']}")
    cfg = ModelConfig(
        name=name, family="dense",
        d_model=hf["hidden_size"], num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        pattern=("dense",), qkv_bias=True, rope_theta=hf["rope_theta"],
        norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"], dtype=hf["torch_dtype"])
    return cfg


def smoke_hf(hf: dict) -> dict:
    """The config at a width the CPU tests can run, with the architecture
    kept: GQA stays grouped, MHA stays whole."""
    kv = 2 if hf["num_key_value_heads"] < hf["num_attention_heads"] else 4
    return dict(hf, hidden_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=kv,
                intermediate_size=256, vocab_size=512)


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a Qwen2-architecture model that fix its work."""
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    tied: bool
    itemsize: int = 2

    @classmethod
    def from_hf(cls, hf: dict) -> "Shape":
        return cls(d=hf["hidden_size"], f=hf["intermediate_size"],
                   heads=hf["num_attention_heads"],
                   kv_heads=hf["num_key_value_heads"],
                   head_dim=hf["hidden_size"] // hf["num_attention_heads"],
                   layers=hf["num_hidden_layers"], vocab=hf["vocab_size"],
                   tied=hf["tie_word_embeddings"])

    def projections(self) -> "list[tuple[int, int, bool]]":
        """(K, N, bias) of each projection of one layer: q, k, v, o, gate,
        up, down."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return [(self.d, q, True), (self.d, kv, True), (self.d, kv, True),
                (q, self.d, False), (self.d, self.f, False),
                (self.d, self.f, False), (self.f, self.d, False)]

    def layer_params(self) -> int:
        return sum(k * n for k, n, _ in self.projections())

    def head_params(self) -> int:
        return self.vocab * self.d

    def streamed_params(self) -> int:
        """Parameters a step reads: every layer's projections and the LM
        head (tied or not, the head is read whole; the embedding rows a
        step looks up are negligible)."""
        return self.layers * self.layer_params() + self.head_params()

    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.itemsize


def gpp_calls(shape: Shape, M: int) -> Work:
    """Every `gpp_matmul` call of one step program over M rows."""
    one = Work()
    for K, N, bias in shape.projections():
        one = one + gpp_call(M, K, N, itemsize=shape.itemsize, bias=bias)
    return Work(one.flops * shape.layers, one.bytes * shape.layers)


def attn_calls(shape: Shape, contexts, S: int) -> Work:
    """Every `paged_attention` call of one step program."""
    one = paged_attn_call(contexts, S, shape.heads, shape.kv_heads,
                          shape.head_dim, itemsize=shape.itemsize)
    return Work(one.flops * shape.layers, one.bytes * shape.layers)


def model_flops(shape: Shape, tokens: int, attn: Work) -> float:
    """Model operations of a step that computes `tokens` rows through every
    layer and the LM head on `tokens` rows: 2 per activated parameter per
    row (dense: every streamed parameter is activated), plus attention.
    Pad rows and inactive lanes are not work."""
    return 2.0 * shape.streamed_params() * tokens + attn.flops


def _step(shape: Shape, gpp: Work, attn: Work, flops: float) -> dict:
    return {"gpp": gpp, "attn": attn,
            "gpp_calls": len(shape.projections()) * shape.layers,
            "attn_calls": shape.layers, "model_flops": flops}


def decode_step(shape: Shape, slots: int, lanes) -> dict:
    """One decode call: `slots` rows through the projections (the call's
    shape), `lanes` the live context of each active lane; the inactive
    lanes attend to 1 row, the null row they touch."""
    contexts = list(lanes) + [1] * (slots - len(lanes))
    return _step(shape, gpp_calls(shape, slots),
                 attn_calls(shape, contexts, 1),
                 model_flops(shape, len(lanes), attn_calls(shape, lanes, 1)))


def prefill_chunk(shape: Shape, rows: int, start: int, real: int) -> dict:
    """One prefill-chunk call: `rows` rows (the call's shape) at positions
    start.. of one lane, of which the first `real` are prompt tokens."""
    return _step(shape, gpp_calls(shape, rows),
                 attn_calls(shape, [start + rows], rows),
                 model_flops(shape, real, attn_calls(shape, [start + real],
                                                     real)
                             if real else Work()))
