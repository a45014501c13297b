"""Per cent of its roofline that the jitted decode program reaches in
the traced window, its work counted per decoded token (kernels layer);
see ``harness.context.roofline``."""
from harness.context import roofline


def read(ctx):
    return roofline(ctx, "decode")
