"""Seconds from the start of the run's process to the window: loading,
weights, engine, compiling or loading the step programs, warm-up, and any
set-up the traffic needs (host clock)."""


def read(ctx):
    return ctx.setup_s
