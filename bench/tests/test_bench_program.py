"""``harness.program``: the readings of the program's own spans, on
synthetic spans and a synthetic trace, against values worked out by
hand."""
import pytest

from harness import program
from reader_cases.program_spans import clocked, recorded
from repro import obs


@pytest.fixture(autouse=True)
def recorder():
    with recorded():
        yield


CASES = {
    # lock waits 10, 110 and 1 ms of invocations 1-3: the 3rd of 3
    "lock_wait_p95_ms": 110.0,
    # queue waits 0.10 + 0.05 + 0.30 s, 0.1 s of invocation 3's throttled
    "throttled_wait_share": 100.0 * 0.1 / 0.45,
    # executes of 0.1 s each, waiting 0.06 + 0.05 + 0.04 s
    "execute_host_share": 50.0,
    # events from 0.2 s (a module) to 1.8 s (a module); work at
    # 0.90-1.213 and 1.50-1.8 s of the trace (offset 99.5 s), 0.613 s,
    # less the modules inside it (0.1 + 0.2 s), over 1.6 s
    "device_idle_with_work_share": 100.0 * 0.313 / 1.6,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reading(name):
    assert getattr(program, name)(clocked()) == pytest.approx(CASES[name])


@pytest.mark.parametrize("name", sorted(CASES))
def test_reads_nothing_without_spans(name):
    obs.RECORDER.clear()
    assert getattr(program, name)(clocked()) is None


def test_idle_with_work_reads_nothing_without_a_trace():
    assert program.device_idle_with_work_share(clocked(trace=False)) is None


def test_invocations_keep_the_windows_completed_ones():
    invs = program.invocations(clocked())
    assert sorted(invs) == [1, 2, 3]
    assert "inv.upload" in invs[3] and "inv.upload" not in invs[1]
