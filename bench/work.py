"""The work a call needs, from shapes alone: operations and HBM bytes of one
`gpp_matmul` call and of one `paged_attention` call.  Which calls a step of
a model makes is its architecture's (`bench/arch/<model_type>.py`).

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
