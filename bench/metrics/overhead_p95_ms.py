"""95th percentile of ``Invocation.overhead``: dispatch to the start of
execution in the wall-clock executor (endpoint lock wait, compile,
upload)."""
from harness.stats import quantile


def read(ctx):
    v = quantile([r.overhead for r in ctx.done()], 0.95)
    return None if v is None else 1e3 * v
