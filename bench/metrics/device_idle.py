"""Share of the traced span in which no operation ran on the device
(device trace: 1 - busy union / span)."""
from bench.stats import on_device


def read(ctx):
    if not on_device(ctx):
        return None
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
