"""Readings of the program's own spans (``repro.obs``): the queue's
throttled share, the endpoint lock's wait, the host's share of execute
and the device's idle time while work waits. Each takes a run's
``Context`` and reads None where the program records no such span, as a
program without ``repro.obs`` does. A metric's reader in ``metrics/``
returns one of them."""
from __future__ import annotations

from typing import Dict, List, Optional

from harness import clock
from harness.stats import quantile
from harness.tracing import length, overlap, union


def _recorder():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.RECORDER


def spans(name: str) -> Optional[List]:
    """Every recorded span named ``name``; None where the program has no
    recorder."""
    rec = _recorder()
    return None if rec is None else rec.spans(name)


def invocations(ctx) -> Optional[Dict[int, Dict[str, object]]]:
    """``inv_id -> {span name: span}`` of the invocations that arrived
    inside ``ctx.window`` (their ``inv.queue`` starts there) and completed
    (they have an ``inv.complete``): the population of ``ctx.done()``.
    None where the program records no such invocation."""
    rec = _recorder()
    if rec is None:
        return None
    lo, hi = ctx.window
    ids = {s.inv for s in rec.spans("inv.queue", lo, hi)}
    out: Dict[int, Dict[str, object]] = {}
    for s in rec.spans(lo=lo):
        if s.inv in ids:
            out.setdefault(s.inv, {})[s.name] = s
    out = {k: g for k, g in out.items() if "inv.complete" in g}
    return out or None


def lock_wait_p95_ms(ctx) -> Optional[float]:
    """95th percentile, in ms, of ``inv.lock_wait``: from a worker's start
    to holding its endpoint's lock, which another invocation of the same
    function may hold for a whole execute (executor layer; part of
    ``Invocation.overhead``)."""
    invs = invocations(ctx)
    if invs is None:
        return None
    v = quantile([g["inv.lock_wait"].end - g["inv.lock_wait"].start
                  for g in invs.values() if "inv.lock_wait" in g], 0.95)
    return None if v is None else 1e3 * v


def execute_host_share(ctx) -> Optional[float]:
    """Per cent of the window's ``inv.execute`` time in which the endpoint
    thread was not blocked on device results, i.e. on the host: 100 x (1 -
    the executes' summed ``device_wait_s`` over their summed durations).
    A wait can also cover another function's device work, so where
    executes overlap this is a lower bound on host time."""
    invs = invocations(ctx)
    if invs is None:
        return None
    ex = [g["inv.execute"] for g in invs.values()]
    dur = sum(s.end - s.start for s in ex)
    if dur <= 0:
        return None
    return 100.0 * (1.0 - sum(s.attrs.get("device_wait_s", 0.0)
                              for s in ex) / dur)


def throttled_wait_share(ctx) -> Optional[float]:
    """Per cent of the window's queue wait (``inv.queue``) that overlaps a
    ``mqfq.throttled`` span of the same function: time its MQFQ queue's
    virtual time ran T ahead of the global one (scheduler layer)."""
    invs = invocations(ctx)
    thr = spans("mqfq.throttled")
    if invs is None or thr is None:
        return None
    by_fn: Dict[str, List] = {}
    for s in thr:
        by_fn.setdefault(s.fn, []).append((s.start, s.end))
    total = held = 0.0
    for g in invs.values():
        q = g["inv.queue"]
        total += q.end - q.start
        held += sum(max(0.0, min(e, q.end) - max(s, q.start))
                    for s, e in by_fn.get(q.fn, ()))
    return None if total <= 0 else 100.0 * held / total


def device_idle_with_work_share(ctx) -> Optional[float]:
    """Per cent of the traced window in which no XLA module ran on the
    chip while some invocation lay between its ``inv.queue`` start and its
    ``inv.complete`` end (device layer): idle time with work waiting,
    which the host and not a lack of demand is to blame for. The spans are
    put on the trace's clock by ``clock.offset``. The window is cut to the
    stretch from the trace's first recorded event to its last, on any
    plane: the profiler records nothing for a few hundred ms before it
    stops, and a module run there would not be seen."""
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.modules:
        return None
    off = clock.offset(ctx)
    invs = invocations(ctx)
    if off is None or invs is None:
        return None
    events = [iv for d in t.devices for iv in t.busy(d)]
    events += [iv for v in t.spans.values() for iv in v]
    lo = max(0.0, min(s for s, _ in events))
    hi = min(t.window_s, max(e for _, e in events))
    if hi <= lo:
        return None
    work = union([(max(g["inv.queue"].start - off, lo),
                   min(g["inv.complete"].end - off, hi))
                  for g in invs.values()])
    work = [(s, e) for s, e in work if e > s]
    idle = [length(work) - sum(overlap(work, b) for b in t.busy(d))
            for d in t.devices]
    return 100.0 * sum(idle) / (len(idle) * (hi - lo))
