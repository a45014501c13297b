"""Cases of the granite-h-warm reader that puts the program's spans on
the trace's clock: the recorded spans and clocked trace of
``program_spans``."""
from reader_cases.program_spans import context  # noqa: F401

CASES = {
    # as device_idle_with_work_share: work at 0.90-1.213 and 1.50-1.8 s
    # of the trace less the modules inside it (0.1 + 0.2 s), over 1.6 s
    "device_idle_with_work_share.hyb": 100.0 * 0.313 / 1.6,
}
