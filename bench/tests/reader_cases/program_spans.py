"""Cases of the readers of the program's own spans (``repro.obs``),
which return ``harness.program``'s readings: synthetic spans in
``repro.obs.RECORDER`` and a synthetic trace."""
import contextlib

from harness.context import Context, Record
from harness.tracing import Trace
from repro import obs


def program_spans():
    """The program's spans (host clock) of the window (100-110 s): three
    invocations that completed, one warm-up before the window and one
    that never completed, which the readings leave out."""
    chain = ("inv.queue", "inv.handoff", "inv.lock_wait", "inv.upload",
             "inv.execute", "inv.complete")
    invs = {
        0: ("a", [90.0, 90.1, 90.101, 90.102, None, 90.2, 90.201], 0.05),
        # lock wait 10 ms; execute 100 ms, 60 ms of it waiting on the chip
        1: ("a", [100.40, 100.50, 100.501, 100.511, None, 100.611,
                  100.612], 0.06),
        # lock wait 110 ms: the first held the lock
        2: ("a", [100.45, 100.50, 100.502, 100.612, None, 100.712,
                  100.713], 0.05),
        # queued 300 ms, 100 ms of it throttled; an upload before execute
        3: ("b", [101.00, 101.30, 101.301, 101.302, 101.402, 101.502,
                  101.503], 0.04),
    }
    out = []
    for inv, (fn, ts, wait) in invs.items():
        names = [n for n, t in zip(chain, ts[1:]) if t is not None]
        ends = [t for t in ts[1:] if t is not None]
        for name, a, b in zip(names, [ts[0]] + ends, ends):
            attrs = {"device_wait_s": wait} \
                if name == "inv.execute" else {}
            out.append(obs.Span(name, inv, fn, a, b, attrs))
    out += [obs.Span("inv.queue", 4, "b", 101.9, 101.95, {}),
            obs.Span("mqfq.throttled", None, "b", 100.9, 101.1, {}),
            obs.Span("mqfq.throttled", None, "a", 99.0, 99.5, {})]
    return out


@contextlib.contextmanager
def recorded():
    """``program_spans()`` in the recorder, cleared again on exit."""
    obs.RECORDER.clear()
    for s in program_spans():
        obs.RECORDER.add(s)
    try:
        yield
    finally:
        obs.RECORDER.clear()


def rec(due):
    return Record(fn=0, due=due, release=due, completion=due + 0.5,
                  queue_time=0.0, overhead=0.0, service_time=0.1,
                  start_type="warm", failed=False)


def clocked(trace=True):
    """A 2 s trace whose feeder spans start 99.5 s before their releases
    (100.0, 100.3, 101.2), with modules at 0.2-0.3, 1.0-1.1, 1.6-1.8 s
    and nothing recorded after 1.8 s."""
    mods = [("jit__prefill(1)", 0.2, 0.3), ("jit__decode(2)", 1.0, 1.1),
            ("jit__decode(2)", 1.6, 1.8)]
    spans = {"feeder": [(0.5, 0.5001), (0.8, 0.8001), (1.7, 1.7001)]}
    return Context(records=[rec(100.0), rec(100.3), rec(101.2)],
                   window=(100.0, 110.0), setup_s=0.0, request={}, work={},
                   peak={},
                   trace=Trace(2.0, {"/device:TPU:0": mods}, spans)
                   if trace else None)


@contextlib.contextmanager
def context(traced=True):
    with recorded():
        yield clocked(traced)


# the values of ``test_bench_program.py``'s cases of the same readings
CASES = {
    # executes of 0.1 s each, waiting 0.06 + 0.05 + 0.04 s
    "execute_host_share.lat": 50.0,
    "execute_host_share.tput": 50.0,
    # queue waits 0.10 + 0.05 + 0.30 s, 0.1 s of invocation 3's throttled
    "throttled_wait_share.tput": 100.0 * 0.1 / 0.45,
    # events from 0.2 s (a module) to 1.8 s (a module); work at
    # 0.90-1.213 and 1.50-1.8 s of the trace (offset 99.5 s), 0.613 s,
    # less the modules inside it (0.1 + 0.2 s), over 1.6 s
    "device_idle_with_work_share": 100.0 * 0.313 / 1.6,
    "device_idle_with_work_share.tput": 100.0 * 0.313 / 1.6,
}
