"""Median ``Invocation.service_time`` in granite-h-warm: one
``JaxEndpoint.execute``, the Mamba-2 + attention prefill and the decode
loop over KV and state, ending in one host read (endpoint layer)."""
from harness.stats import quantile


def read(ctx):
    v = quantile([r.service_time for r in ctx.done()], 0.5)
    return None if v is None else 1e3 * v
