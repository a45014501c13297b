"""Invocations completed, not failed, inside the window, over its
seconds."""


def read(ctx):
    lo, hi = ctx.window
    n = sum(1 for r in ctx.done() if lo <= r.completion <= hi)
    return n / ctx.seconds
