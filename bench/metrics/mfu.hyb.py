"""Per cent of the chip's bf16 peak that the model operations of the
executes in granite-h-warm's traced window make over the window (whole
step)."""
from harness.context import mfu


def read(ctx):
    return mfu(ctx)
