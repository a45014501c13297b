"""Per cent of the window's queue wait that its function's MQFQ queue
spent throttled (scheduler layer), in the cells that report
``throughput_inv_s``; see ``harness.program.throttled_wait_share``."""
from harness import program


def read(ctx):
    return program.throttled_wait_share(ctx)
