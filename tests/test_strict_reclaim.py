"""Second-pass resident reclaim semantics (ROADMAP "Second-pass
reclaim", closed by ``ServerConfig.strict_reclaim``).

When the evictable pool cannot satisfy a request, the seed's fallback
re-walked its *pre-eviction* resident snapshot, re-processing the
phase-1 victims: their eviction is a residency no-op but the byte
accounting and evict-listener callbacks fire a second time.
``strict_reclaim=True`` replays that bug-for-bug — pinned here and by
tests/test_memory_equivalence.py against the reference layer (which IS
the seed and is always strict; the config flag only affects the indexed
layer). ``strict_reclaim=False`` (the ``ServerConfig`` default since
PR 6) retires the quirk on the indexed manager: the second pass sweeps
only regions still resident, so every victim is evicted, counted and
notified exactly once, while freeing the same memory."""
from repro.memory.manager import GB, DeviceMemoryManager
from repro.memory.reference import ReferenceDeviceMemoryManager
from repro.server import ServerConfig, make_server
from repro.workloads.spec import DEFAULT_MIX, function_copies
from repro.workloads.traces import azure_trace


def _pressured(strict: bool) -> DeviceMemoryManager:
    """Force the second pass: A is evictable (3 GB), B and C are
    resident but active (3 GB each, cap 10 GB); acquiring 9 GB for D
    frees A in phase 1 (4 GB free < 9) and must fall back to the
    resident sweep for B and C."""
    m = DeviceMemoryManager(10 * GB, policy="prefetch",
                            strict_reclaim=strict)
    log = []
    m.residency_listeners.append(
        lambda fn, res: res or log.append(fn))
    m.acquire("A", 3 * GB, 1.0)
    m.acquire("B", 3 * GB, 2.0)
    m.acquire("C", 3 * GB, 3.0)
    m.on_queue_idle("A", 3.5)          # prefetch: evictable, no swap-out
    m.log = log
    return m


def test_strict_replays_double_counted_victims():
    m = _pressured(strict=True)
    m.acquire("D", 9 * GB, 4.0)
    # phase 1 evicts A; the strict second pass re-walks the
    # pre-snapshot: A again (duplicate accounting), then B, then C
    assert m.log == ["A", "A", "B", "C"]
    assert m.bytes_evicted == 12 * GB            # 3 counted twice
    assert m.is_resident("D", 10.0)
    assert not any(m.regions[f].resident for f in "ABC")


def test_clean_reclaim_counts_each_victim_once():
    m = _pressured(strict=False)
    m.acquire("D", 9 * GB, 4.0)
    assert m.log == ["A", "B", "C"]              # no duplicates
    assert m.bytes_evicted == 9 * GB
    # identical end state: same residency, same free memory
    assert m.is_resident("D", 10.0)
    assert not any(m.regions[f].resident for f in "ABC")
    assert m.used == 9 * GB


def test_strict_matches_reference_bug_for_bug():
    """The default mode replays the seed exactly on the forced-fallback
    scenario (the op-level fuzz in test_memory_equivalence.py covers the
    broad surface; this pins the quirk itself)."""
    ref = ReferenceDeviceMemoryManager(10 * GB, policy="prefetch")
    log = []
    ref.residency_listeners.append(
        lambda fn, res: res or log.append(fn))
    ref.acquire("A", 3 * GB, 1.0)
    ref.acquire("B", 3 * GB, 2.0)
    ref.acquire("C", 3 * GB, 3.0)
    ref.on_queue_idle("A", 3.5)
    ref.acquire("D", 9 * GB, 4.0)

    m = _pressured(strict=True)
    m.acquire("D", 9 * GB, 4.0)
    assert m.log == log
    assert m.bytes_evicted == ref.bytes_evicted
    assert m.used == ref.used


def test_reference_layer_stays_strict_regardless_of_flag():
    """The reference layer is the executable seed: it has no
    strict_reclaim knob and replays the double-count sweep whatever the
    config says, so reference-layer configs keep working under the
    clean-reclaim default and equivalence suites opt the indexed side
    back in explicitly."""
    fns = function_copies(DEFAULT_MIX, 4)
    for flag in (False, True):
        srv = make_server(ServerConfig(device_layer="reference",
                                       batch_dispatch=False,
                                       strict_reclaim=flag), fns=fns)
        mgr = srv.control.devices[0].mem
        assert isinstance(mgr, ReferenceDeviceMemoryManager)
        assert not hasattr(mgr, "strict_reclaim")


def test_indexed_layer_follows_config_flag():
    fns = function_copies(DEFAULT_MIX, 4)
    for flag in (False, True):
        srv = make_server(ServerConfig(strict_reclaim=flag), fns=fns)
        assert srv.control.devices[0].mem.strict_reclaim is flag
    # unconfigured default retires the double-count quirk
    srv = make_server(ServerConfig(), fns=fns)
    assert srv.control.devices[0].mem.strict_reclaim is False


def test_clean_reclaim_full_stack_under_pressure():
    """A pressured end-to-end run with the quirk retired still
    completes every invocation and never double-counts: evicted bytes
    are bounded by uploads (every eviction had a matching upload)."""
    fns = function_copies(DEFAULT_MIX, 12)
    trace = azure_trace(fns, duration=150.0, trace_id=3)
    cfg = ServerConfig(policy="mqfq-sticky", policy_kwargs={"T": 5.0},
                       d=2, n_devices=2, capacity_bytes=3 * GB,
                       pool_size=8, mem_policy="prefetch",
                       strict_reclaim=False)
    res = make_server(cfg, fns=fns).run_trace(trace)
    assert res.completed_count == len(trace)
    for d in res.devices:
        assert d.mem.bytes_evicted <= d.mem.bytes_uploaded
