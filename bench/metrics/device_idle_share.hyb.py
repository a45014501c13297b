"""Per cent of granite-h-warm's traced window in which no XLA module ran
on the chip (device layer); see ``harness.context.idle_share``."""
from harness.context import idle_share


def read(ctx):
    return idle_share(ctx)
