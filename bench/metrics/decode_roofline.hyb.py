"""Per cent of its roofline that the jitted decode loop of the Mamba-2 +
attention stack reaches in the traced window, per decoded token (kernels
layer); see ``harness.context.roofline``."""
from harness.context import roofline


def read(ctx):
    return roofline(ctx, "decode")
