"""Shared arithmetic of the metric readers: percentiles, and the samples
the host clock gives over the window."""
from __future__ import annotations

import math


def percentile(samples, q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]) of raw samples, as
    numpy's default method (copied from repro.obs.metrics.percentile)."""
    if not samples:
        return math.nan
    if not 0.0 <= q <= 100.0:
        raise ValueError("q in [0, 100]")
    xs = sorted(samples)
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * (q / 100.0)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


def itls_s(ctx) -> "list[float]":
    """Every gap between two consecutive tokens of one request, both in the
    window."""
    out = []
    for ts in ctx.tokens.values():
        out += [b - a for a, b in zip(ts, ts[1:]) if a >= ctx.t0]
    return out


def on_device(ctx) -> bool:
    """Whether the run traced a chip (a CPU rehearsal reads no device
    metric)."""
    return bool(ctx.peaks is not None and ctx.trace is not None
                and ctx.trace.ops)


def traced_work(ctx):
    """Per traced step program: the work its kernels and its model need,
    and how many calls of each kernel it makes (`ctx.arch`'s
    `prefill_chunk` and `decode_step`), as lists over the prefill and the
    decode calls."""
    pre, dec = [], []
    for step in ctx.traced_steps():
        if step["prefill"] is not None:
            start, rows, real = step["prefill"]
            pre.append(ctx.arch.prefill_chunk(ctx.shape, rows, start, real))
        if step["decode"]:
            dec.append(ctx.arch.decode_step(ctx.shape, ctx.slots,
                                            step["decode"]))
    return pre, dec
