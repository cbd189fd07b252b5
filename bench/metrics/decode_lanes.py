"""Decode lanes per decode call over the window: the engine's ledger's
decode tokens over its steps that decoded (a count)."""


def read(ctx):
    calls = [r["decode_tokens"] for r in ctx.rows if r["decode_tokens"]]
    return sum(calls) / len(calls) if calls else None
