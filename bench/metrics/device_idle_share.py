"""Per cent of the traced window in which no XLA module ran on the chip
(device layer), in the cells that report latency; see
``harness.context.idle_share``."""
from harness.context import idle_share


def read(ctx):
    return idle_share(ctx)
