"""Per cent of granite-h-warm's ``inv.execute`` time spent on the host,
not blocked on the chip's results (endpoint layer); see
``harness.program.execute_host_share``."""
from harness import program


def read(ctx):
    return program.execute_host_share(ctx)
