"""Weight bytes the step programs copied into zero-padded operands of
`gpp_matmul` / `gpp_matmul_grouped` per step over the window, in MB
(1e6 B): the mean of the engine's ledger `gpp_pad_bytes`, counted on the
host from the weights' shapes and the kernels' tile plans.  Read on a chip
only: a CPU rehearsal serves smoke widths, whose pads say nothing of the
served weights'.  Nothing is read from an engine whose ledger has no such
counter."""
from bench.stats import on_device


def read(ctx):
    if not on_device(ctx):
        return None
    pad = [r.get("gpp_pad_bytes") for r in ctx.rows]
    if not pad or None in pad:
        return None
    return sum(pad) / len(pad) / 1e6
