"""The decode step's model operations (2 per activated parameter per active
lane, plus attention over each lane's live context; counted by the
architecture's step work, bench/arch) over its device time at the chip's
peak (device trace)."""
from bench import trace_reduce
from bench.stats import on_device, traced_work


def read(ctx):
    if not on_device(ctx):
        return None
    tot, n = trace_reduce.module_time(ctx.trace, "_decode")
    _, dec = traced_work(ctx)
    if not n or n != len(dec):
        return None
    flops = sum(w["model_flops"] for w in dec)
    return 100.0 * flops / (tot / 1e9 * ctx.peaks.flops_per_s)
