"""``harness.clock``: the offset between the device trace's clock and the
host's monotonic clock, from the feeder's spans and releases."""
import numpy as np
import pytest

from harness import clock
from harness.context import Context, Record
from harness.tracing import Trace


def _ctx(releases, feeds, window_s=4.0):
    recs = [Record(fn=0, due=r, release=r, completion=r + 0.2,
                   queue_time=0.0, overhead=0.0, service_time=0.1,
                   start_type="warm", failed=False) for r in releases]
    trace = Trace(window_s, {}, {"feeder": [(f, f + 1e-4) for f in feeds]})
    return Context(records=recs, window=(releases[0], releases[-1] + 1.0),
                   setup_s=0.0, request={}, work={}, peak={}, trace=trace)


def _arrivals(n=240, seconds=50.0, seed=3):
    rng = np.random.default_rng(seed)
    return np.sort(rng.uniform(1000.0, 1000.0 + seconds, n))


def test_finds_a_known_offset():
    rel = _arrivals()
    off = 1019.987654
    # the trace holds the 4 s from ``off``; each span starts a few us
    # after its release, and one span (a late arrival) has no release
    inside = rel[(rel >= off) & (rel <= off + 3.9)]
    rng = np.random.default_rng(7)
    feeds = list(inside - off + rng.uniform(2e-6, 2e-4, len(inside)))
    feeds.append(3.99)
    got = clock.offset(_ctx(rel, feeds))
    assert got == pytest.approx(off, abs=2e-4)


def test_refuses_feeder_spans_that_match_nothing():
    rel = _arrivals()
    # spans 0.37 s apart: no shift puts 80% of them within 1 ms of a
    # release
    feeds = list(np.arange(0.1, 4.0, 0.37))
    assert clock.offset(_ctx(rel, feeds)) is None


def test_refuses_an_offset_that_a_shift_matches_as_well():
    rel = np.arange(100.0, 110.0, 1.0)
    # one span: every release fits it equally well
    assert clock.offset(_ctx(rel, [0.5])) is None


def test_reads_nothing_without_a_trace_or_feeder_spans():
    rel = _arrivals()
    c = _ctx(rel, [])
    assert clock.offset(c) is None
    c.trace = None
    assert clock.offset(c) is None
