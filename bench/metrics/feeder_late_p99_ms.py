"""99th percentile of how late the benchmark's feeder released an
arrival after its due time (entry layer)."""
from harness.stats import quantile


def read(ctx):
    v = quantile([r.release - r.due for r in ctx.records], 0.99)
    return None if v is None else 1e3 * v
