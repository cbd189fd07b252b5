"""gpp_matmul's share of its roofline over the traced span: the least time
of every call (the larger of its operations at peak FLOP/s and its bytes
at peak HBM bandwidth, counted by the architecture's step work,
bench/arch) over the kernel's device time, with the ops that staged its
operands into VMEM (they read its weights from HBM).  Nothing is read when
the trace's calls do not match the traced steps' `gpp_calls`."""
from bench import trace_reduce
from bench.stats import on_device, traced_work


def read(ctx):
    if not on_device(ctx):
        return None
    tot, n = trace_reduce.staged_kernel_time(ctx.trace, "gpp_matmul")
    pre, dec = traced_work(ctx)
    if not n or n != sum(w["gpp_calls"] for w in pre + dec):
        return None
    p = ctx.peaks
    least = sum(w["gpp"].least_s(p.flops_per_s, p.hbm_bytes_per_s)
                for w in pre + dec)
    return 100.0 * least / (tot / 1e9)
