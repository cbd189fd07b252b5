"""Share of the routed-expert matmuls' rows that carry a token over the
window: the engine's ledger's rows routed (real tokens x experts per token
x MoE layers) over the rows its grouped matmuls multiply (MoE layers x
experts x groups x capacity), summed over the window's steps, in %.
Nothing is read from an engine whose ledger has no such counters, or that
multiplied no expert row."""


def read(ctx):
    routed = [r.get("moe_rows_routed") for r in ctx.rows]
    computed = [r.get("moe_rows_computed") for r in ctx.rows]
    if not ctx.rows or None in routed or None in computed:
        return None
    total = sum(computed)
    return 100.0 * sum(routed) / total if total else None
