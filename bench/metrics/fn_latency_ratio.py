"""Fairness across functions: the largest per-function median latency
over the median latency of all invocations (1 is even)."""
from harness.stats import quantile


def read(ctx):
    done = ctx.done()
    overall = quantile([r.latency for r in done], 0.5)
    if not overall:
        return None
    by_fn = {}
    for r in done:
        by_fn.setdefault(r.fn, []).append(r.latency)
    return max(quantile(v, 0.5) for v in by_fn.values()) / overall
