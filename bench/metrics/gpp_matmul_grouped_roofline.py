"""gpp_matmul_grouped's share of its roofline over the traced span: the
least time of every call (the larger of its operations at peak FLOP/s and
its bytes at peak HBM bandwidth, counted by the architecture's step work,
bench/arch: the bytes of every expert's weights, the FLOPs of the rows that
carry a token) over the kernel's device time, with the ops that staged its
operands into VMEM.  Nothing is read when the trace's calls do not match
the traced steps' `grouped_calls`, or the architecture counts none."""
from bench import trace_reduce
from bench.stats import on_device, traced_work


def read(ctx):
    if not on_device(ctx):
        return None
    tot, n = trace_reduce.staged_kernel_time(ctx.trace, "gpp_matmul_grouped")
    steps = [w for ws in traced_work(ctx) for w in ws]
    if not n or not all("grouped" in w for w in steps):
        return None
    if n != sum(w["grouped_calls"] for w in steps):
        return None
    p = ctx.peaks
    least = sum(w["grouped"].least_s(p.flops_per_s, p.hbm_bytes_per_s)
                for w in steps)
    return 100.0 * least / (tot / 1e9)
