"""Median latency of the window's invocations, from the due time of each
arrival to its completion; a failed invocation counts as infinitely late."""
from harness.stats import quantile


def read(ctx):
    lat = [float("inf") if r.failed or r.latency is None else r.latency
           for r in ctx.records]
    v = quantile(lat, 0.50)
    return None if v is None else 1e3 * v
