"""Per cent of the window's invocations that started host-warm or cold,
i.e. behind a host-to-HBM upload (memory manager layer)."""


def read(ctx):
    if not ctx.records:
        return None
    n = sum(1 for r in ctx.records if r.start_type in ("host_warm", "cold"))
    return 100.0 * n / len(ctx.records)
