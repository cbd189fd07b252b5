"""Device time of the decode step program per call over the traced span:
the total of its runs over their count (device trace)."""
from bench import trace_reduce
from bench.stats import on_device


def read(ctx):
    if not on_device(ctx):
        return None
    tot, n = trace_reduce.module_time(ctx.trace, "_decode")
    return tot / n / 1e6 if n else None
