"""Median ``Invocation.service_time``: one ``JaxEndpoint.execute``,
prefill and the decode loop, ending in a host sync (endpoint layer)."""
from harness.stats import quantile


def read(ctx):
    v = quantile([r.service_time for r in ctx.done()], 0.5)
    return None if v is None else 1e3 * v
