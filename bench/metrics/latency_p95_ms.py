"""95th percentile of the same population as ``latency_p50_ms``: every
invocation of the window, due time to completion."""
from harness.stats import quantile


def read(ctx):
    lat = [float("inf") if r.failed or r.latency is None else r.latency
           for r in ctx.records]
    v = quantile(lat, 0.95)
    return None if v is None else 1e3 * v
