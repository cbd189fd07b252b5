"""95th percentile of every gap between consecutive output tokens of a
request, over all requests, both tokens in the window (host clock)."""
from bench.stats import itls_s, percentile


def read(ctx):
    xs = itls_s(ctx)
    return 1e3 * percentile(xs, 95) if xs else None
