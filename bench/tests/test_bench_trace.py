"""The trace reduction on a small recorded trace: 1.5 s of a window of
four qwen3-1.7b functions over an HBM budget of three, profiled on a TPU
v5 lite, cut down to the device's XLA Modules line and the benchmark's
host spans (data/qwen3-trace.xplane.pb). The expected sums were taken
from the full recorded trace over the same 1.5 s."""
import os

import pytest

from harness import tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "qwen3-trace.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tracing.reduce(FIXTURE)


def test_window_devices_and_spans(trace):
    assert trace.window_s == pytest.approx(1.5)
    assert trace.devices == ["/device:TPU:0"]
    assert len(trace.modules["/device:TPU:0"]) == 267
    assert {k: len(v) for k, v in trace.spans.items()} == {
        "compile": 0, "upload": 0, "execute": 4, "dispatch": 42, "feeder": 8}
    assert len(trace.executes()) == 4


def test_busy_time_is_the_union_of_module_runs(trace):
    assert trace.busy_s == pytest.approx(0.475443313, rel=1e-6)


def test_device_ops_sum_module_runs_by_name(trace):
    ops = dict(trace.device_ops())
    assert ops["jit__decode"] == pytest.approx(0.437388940, rel=1e-6)
    assert ops["jit__prefill"] == pytest.approx(0.037420928, rel=1e-6)
    assert list(ops)[:2] == ["jit__decode", "jit__prefill"]


def test_module_time_inside_execute_spans(trace):
    ex = trace.executes()
    assert 0 < trace.module_time("prefill", ex) <= 0.037420928 + 1e-9
    assert 0 < trace.module_time("decode", ex) <= 0.437388940 + 1e-9


def test_idle_gaps_tile_the_idle_time(trace):
    gaps = trace.idle_gaps(top=10**6)
    assert sum(g for _, g in gaps) == pytest.approx(
        trace.window_s - trace.busy_s, rel=1e-6)
    assert {name for name, _ in gaps} <= set(tracing.SPANS) | {"no_span"}
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
