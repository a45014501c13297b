"""Per cent of granite-h-warm's traced window in which the chip ran
nothing while an invocation waited or ran (device layer); see
``harness.program.device_idle_with_work_share``."""
from harness import program


def read(ctx):
    return program.device_idle_with_work_share(ctx)
