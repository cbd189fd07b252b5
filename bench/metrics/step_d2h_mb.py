"""Bytes the engine copied device -> host per step over the window, in MB
(1e6 B): the mean of its ledger's `d2h_bytes` (the logits each step reads
back to sample from).  Read on a chip only: on the CPU the device's arrays
are host memory and nothing crosses a link.  Nothing is read from an
engine whose ledger has no such counter."""
from bench.stats import on_device


def read(ctx):
    if not on_device(ctx):
        return None
    d2h = [r.get("d2h_bytes") for r in ctx.rows]
    if not d2h or None in d2h:
        return None
    return sum(d2h) / len(d2h) / 1e6
