"""The work a call needs, from shapes alone: operations and HBM bytes of one
`gpp_matmul` call, one `paged_attention` call, and the calls of one
serving step of a Qwen2-architecture model (read from its config.json keys).

These count what the call needs — each weight, each live KV row, each
activation read once and each output written once — not what an
implementation moves: no pad copies, no pool relayouts, no re-reads.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, flops_per_s: float, bytes_per_s: float) -> float:
        """The least time the chip could take: the larger bound."""
        return max(self.flops / flops_per_s, self.bytes / bytes_per_s)


def gpp_call(M: int, K: int, N: int, *, itemsize: int = 2,
             bias: bool = False) -> Work:
    """y (M, N) = x (M, K) @ w (K, N) [+ bias]: w, x read once, y written
    once (f32 bias)."""
    return Work(2.0 * M * K * N,
                (K * N + M * K + M * N) * itemsize + (4 * N if bias else 0))


def paged_attn_call(contexts, S: int, heads: int, kv_heads: int,
                    head_dim: int, *, itemsize: int = 2) -> Work:
    """One call over lanes whose query rows 0..S-1 sit at the END of each
    lane's context of `contexts[b]` tokens (decode: S = 1 and the context
    includes the new token; a prefill chunk: the chunk's last row sees the
    whole context).  Row s sees `ctx - S + s + 1` keys; QK and PV are 2
    flops per key per head dim each.  Each lane's live K and V rows are
    read once, q read and the output written once."""
    flops = 0.0
    kv_rows = 0
    for ctx in contexts:
        seen = sum(ctx - S + s + 1 for s in range(S))
        flops += 4.0 * heads * head_dim * seen
        kv_rows += ctx
    qo = 2 * len(contexts) * S * heads * head_dim
    return Work(flops, (2 * kv_rows * kv_heads * head_dim + qo) * itemsize)


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
    layer and the LM head on `tokens` rows: 2 per streamed parameter per
    row, plus attention.  Pad rows and inactive lanes are not work."""
    return 2.0 * shape.streamed_params() * tokens + attn.flops


def decode_step(shape: Shape, slots: int, contexts) -> dict:
    """One decode call: `slots` rows through the projections (the call's
    shape), `contexts` the live context of each lane it serves (inactive
    lanes: 1, the null row they touch).  `active` is how many are real."""
    attn = attn_calls(shape, contexts, 1)
    return {"gpp": gpp_calls(shape, slots), "attn": attn}


def prefill_chunk(shape: Shape, chunk: int, start: int) -> dict:
    """One prefill-chunk call: `chunk` rows at positions start.. of one lane."""
    attn = attn_calls(shape, [start + chunk], chunk)
    return {"gpp": gpp_calls(shape, chunk), "attn": attn}
