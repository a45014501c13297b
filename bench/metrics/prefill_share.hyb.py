"""Per cent of the device time inside the traced window's ``execute``
spans that modules named ``*prefill*`` take (kernels layer): where the
Mamba-2 prefill's sequential scan sits. None without a trace."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    spans = t.executes()
    total = t.module_time("", spans)
    return None if total <= 0 else 100.0 * t.module_time("prefill", spans) / total
