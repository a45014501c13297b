"""95th percentile of ``Invocation.queue_time``: submit to dispatch by
the MQFQ-Sticky control plane (scheduler layer)."""
from harness.stats import quantile


def read(ctx):
    v = quantile([r.queue_time for r in ctx.records
                  if r.queue_time is not None], 0.95)
    return None if v is None else 1e3 * v
