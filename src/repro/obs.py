"""Spans of the wall-clock served path, kept in memory.

One process-wide ``RECORDER`` holds the last ``MAXLEN`` finished spans,
each ``(name, inv, fn, start, end, attrs)``: ``start`` and ``end`` are
``time.monotonic()`` seconds, the clock of ``WallClockExecutor.now()``
plus its ``t0``. Spans of one invocation share ``inv`` (its
``inv_id``); its memory spans ``inv.queue``, ``inv.handoff``,
``inv.lock_wait``, ``inv.compile`` or ``inv.upload``, ``inv.execute``
and ``inv.complete`` are contiguous, each starting at the clock read
that ended the one before, so their durations sum to completion minus
arrival and each layer's self time can be read off them.

``span`` also opens a ``jax.profiler.TraceAnnotation`` of the same name,
so the span lands in any profiler trace on the device trace's clock;
``record`` writes a span afterwards, from timestamps the program already
took (a wait that starts and ends on different threads). Recording is
always on in the wall-clock executor; the simulator records nothing.
Every name is dotted (``inv.*``, ``mqfq.*``).
"""
from __future__ import annotations

import collections
import sys
import time
from typing import Dict, List, NamedTuple, Optional

MAXLEN = 65536


class Span(NamedTuple):
    name: str
    inv: Optional[int]
    fn: Optional[str]
    start: float
    end: float
    attrs: Dict[str, float]


class Recorder:
    """A bounded buffer of finished spans: the oldest fall out first."""

    def __init__(self, maxlen: int = MAXLEN):
        self._spans: collections.deque = collections.deque(maxlen=maxlen)

    def add(self, span: Span) -> None:
        self._spans.append(span)         # one C call: safe across threads

    def spans(self, name: Optional[str] = None, lo: Optional[float] = None,
              hi: Optional[float] = None) -> List[Span]:
        """Finished spans, oldest first: those named ``name`` (all if
        None) that start inside ``[lo, hi]``."""
        return [s for s in list(self._spans)
                if (name is None or s.name == name)
                and (lo is None or s.start >= lo)
                and (hi is None or s.start <= hi)]

    def clear(self) -> None:
        self._spans.clear()


RECORDER = Recorder()


def record(name: str, inv: Optional[int], fn: Optional[str], start: float,
           end: float, **attrs: float) -> None:
    """Keep a span whose ends were read elsewhere (monotonic seconds)."""
    RECORDER.add(Span(name, inv, fn, start, end, attrs))


class span:
    """``with span(name, inv, fn) as sp:`` records the block as a span
    and annotates it in any running profiler trace. ``start`` makes the
    span begin at an earlier clock read (the end of the span before it),
    so that an invocation's spans are contiguous; ``sp.end`` is the read
    that ended it. ``attrs`` are kept with the span and given to the
    annotation; ``sp.attrs`` may gain counters inside the block, which
    are kept in memory only."""
    __slots__ = ("name", "inv", "fn", "start", "end", "attrs", "_note")

    def __init__(self, name: str, inv: Optional[int] = None,
                 fn: Optional[str] = None, start: Optional[float] = None,
                 **attrs: float):
        self.name, self.inv, self.fn = name, inv, fn
        self.start, self.end, self.attrs = start, None, attrs
        self._note = None

    def __enter__(self) -> "span":
        # a profiler runs only in a process that has loaded JAX; without
        # it there is nothing to annotate, and JAX is not imported for it
        prof = sys.modules.get("jax.profiler")
        if prof is not None:
            meta = dict(self.attrs)
            if self.inv is not None:
                meta["inv"] = self.inv
            if self.fn is not None:
                meta["fn"] = self.fn
            self._note = prof.TraceAnnotation(self.name, **meta)
            self._note.__enter__()
        if self.start is None:
            self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.monotonic()
        if self._note is not None:
            self._note.__exit__(*exc)
        RECORDER.add(Span(self.name, self.inv, self.fn, self.start,
                          self.end, self.attrs))
