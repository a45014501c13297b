"""Cases of the readers of the granite-h-warm cell: the synthetic records
and trace of ``records_and_trace``, and program spans whose
``inv.execute`` carries the cache counters of ``JaxEndpoint.execute``."""
import contextlib

from reader_cases.records_and_trace import ctx
from repro import obs

MB = 1e6


def program_spans():
    """Two invocations of the window (100-110 s) that completed, with
    their cache bytes on ``inv.execute``; one before the window, whose
    bytes the reading leaves out."""
    out = []
    for inv, t0, kv, state in ((0, 90.0, 1 * MB, 1 * MB),
                               (1, 100.5, 8 * MB, 152 * MB),
                               (2, 101.5, 8 * MB, 150 * MB)):
        out += [obs.Span("inv.queue", inv, "a", t0, t0 + 0.1, {}),
                obs.Span("inv.execute", inv, "a", t0 + 0.1, t0 + 0.5,
                         {"device_wait_s": 0.3, "kv_bytes": kv,
                          "state_bytes": state}),
                obs.Span("inv.complete", inv, "a", t0 + 0.5, t0 + 0.501,
                         {})]
    return out


@contextlib.contextmanager
def context(traced=True):
    obs.RECORDER.clear()
    for s in program_spans():
        obs.RECORDER.add(s)
    try:
        yield ctx() if traced else ctx(trace=None)
    finally:
        obs.RECORDER.clear()


CASES = {
    # service times 0.05 .. 0.14 s: the 5th of 10
    "execute_ms.hyb": 90.0,
    # two prefills of max(4e9/1e12, 2e9/1e11) = 20 ms over 10 ms
    "prefill_roofline.hyb": 400.0,
    # 2 x 2 x 4 tokens of max(1 ms, 5 ms) = 80 ms over 60 ms of decode
    "decode_roofline.hyb": 100.0 * 0.080 / 0.060,
    # 2 invocations x (4e9 + 8 x 1e9) over 0.1 s x 1e12
    "mfu.hyb": 100.0 * 2 * 12e9 / 1e11,
    # 10 ms of prefill in 70 ms of modules inside the executes
    "prefill_share.hyb": 100.0 * 0.010 / 0.070,
    # (8 + 152) and (8 + 150) MB
    "carried_cache_mb.hyb": 159.0,
    # two executes of 0.4 s in the window, each waiting 0.3 s
    "execute_host_share.hyb": 25.0,
    # modules run 0-50 and 60-80 ms of the 100 ms trace
    "device_idle_share.hyb": 30.0,
}
