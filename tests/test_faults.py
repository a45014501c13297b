"""Fault-injection and failure-recovery plane (repro.faults, ISSUE 9).

Layered like the subsystem:

  1. plan generation (seeded, fully expanded, bounds)
  2. injector + FaultyEndpoint wrapper + StubEndpoint error paths
  3. datapath abort (retry / drop / abort_all) units
  4. sim: endpoint faults, device faults (transient / permanent),
     transfer faults, shedding — conservation under every one
  5. fault-free differential: an *empty* plan is bit-identical to
     ``faults=None`` (the hooks must not perturb the float path)
  6. recovery-off reference: faults inject, platform does not react,
     goodput collapses
  7. wallclock: endpoint-fault parity with the sim, device-fault
     watchdog, drain-timeout teardown (no leaked threads)
  8. sharded wallclock: vt_sync_errors surfaced, run survives
  9. replay: feeder outages counted, worker errors propagate loudly
 10. chaos scenarios end-to-end + config validation
"""
import threading
import time

import pytest

from repro.datapath import DeviceDataPath
from repro.faults import (DeviceFault, EndpointFault, FaultError,
                          FaultInjector, FaultPlan, FaultyEndpoint,
                          FeederFault, TransferFault)
from repro.memory.manager import GB, DeviceMemoryManager
from repro.server import ServerConfig, StubEndpoint, make_server
from repro.workloads.spec import FunctionSpec
from repro.workloads.traces import TraceEvent

INF = float("inf")


def _fns(n=4, warm=0.05, mem=1 << 20, cold=0.0):
    return {f"f{i}": FunctionSpec(f"f{i}", warm_time=warm, cold_init=cold,
                                  mem_bytes=mem, demand=0.2)
            for i in range(n)}


def _trace(n, gap, n_fns=4):
    return [TraceEvent(gap * i, f"f{i % n_fns}") for i in range(n)]


def _sim_cfg(**kw):
    kw.setdefault("executor", "sim")
    kw.setdefault("n_devices", 2)
    kw.setdefault("sampling", "transition")
    kw.setdefault("batch_dispatch", True)
    kw.setdefault("device_layer", "indexed")
    return ServerConfig(**kw)


def _zero_stranded(rr):
    """Every arrival has a final disposition: completed, explicitly
    failed (dropped / recovery-off error), or shed at the door."""
    for i in rr.invocations:
        assert i.done or i.shed, i
    f = rr.faults
    assert f.accounted == f.arrivals, (f.accounted, f.arrivals)


# ---------------------------------------------------------------------------
# 1. plan generation
# ---------------------------------------------------------------------------


def test_generate_is_deterministic_and_bounded():
    kw = dict(seed=7, horizon_s=100.0, n_devices=4,
              fn_ids=[f"f{i}" for i in range(10)],
              device_faults=3, permanent_devices=1,
              endpoint_fault_frac=0.5, endpoint_faults_per_fn=2,
              transfer_faults=2, feeder_faults=2, n_feeders=3)
    a, b = FaultPlan.generate(**kw), FaultPlan.generate(**kw)
    assert a == b                       # same seed, same schedule
    assert a != FaultPlan.generate(**{**kw, "seed": 8})
    assert len(a.device_faults) == 3
    assert sum(1 for f in a.device_faults if f.duration == INF) == 1
    for f in a.device_faults:
        assert 10.0 <= f.t <= 80.0 and 0 <= f.dev_id < 4
    for f in a.transfer_faults:
        assert 10.0 <= f.t <= 80.0 and 0 <= f.dev_id < 4
    for f in a.feeder_faults:
        assert 0 <= f.shard < 3
    for f in a.endpoint_faults:
        assert f.mode in ("error", "hang")
        assert (f.latency > 0.0) == (f.mode == "hang")
    assert bool(a) and not bool(FaultPlan())


# ---------------------------------------------------------------------------
# 2. injector + endpoint wrapper + stub error paths
# ---------------------------------------------------------------------------


def test_stub_endpoint_refuses_unprepared_execute():
    """StubEndpoint's guard: executing before compile (or after evict)
    is a bug in the caller's residency reconciliation, not a silent
    zero-cost run."""
    ep = StubEndpoint("f", FunctionSpec("f", 0.01, 0.0, 1))
    with pytest.raises(AssertionError):
        ep.execute()                    # never compiled
    ep.compile()
    ep.execute()
    ep.evict()
    with pytest.raises(AssertionError):
        ep.execute()                    # compiled but not resident
    ep.upload()
    ep.execute()
    assert ep.execute_count == 2


def test_faulty_endpoint_injects_on_the_scheduled_attempt():
    plan = FaultPlan(endpoint_faults=(EndpointFault("f", 1, "error"),
                                      EndpointFault("f", 3, "hang", 0.01)))
    inj = FaultInjector(plan)
    ep = FaultyEndpoint(StubEndpoint("f", FunctionSpec("f", 0.0, 0.0, 1)),
                        inj)
    ep.compile()                        # protocol delegation
    assert ep.compiled and ep.resident_on(0) and ep.weight_bytes == 1
    ep.execute()                        # attempt 0: clean
    with pytest.raises(FaultError) as e:
        ep.execute()                    # attempt 1: scheduled error
    assert e.value.mode == "error" and e.value.fn_id == "f"
    ep.execute()                        # attempt 2: clean
    t0 = time.monotonic()
    with pytest.raises(FaultError) as e:
        ep.execute()                    # attempt 3: hang, then killed
    assert e.value.mode == "hang"
    assert time.monotonic() - t0 >= 0.01
    assert inj.endpoint_faults == 2
    # the inner stub only saw the clean attempts
    assert ep._inner.execute_count == 2


def test_injector_device_windows():
    inj = FaultInjector(FaultPlan(device_faults=(
        DeviceFault(1.0, 0, 2.0), DeviceFault(5.0, 0, INF))))
    assert not inj.device_down(0, 0.5)
    assert inj.device_down(0, 1.5) and not inj.device_down(1, 1.5)
    assert inj.device_fault_end(0, 1.5) == 3.0
    assert not inj.device_down(0, 4.0)
    assert inj.device_down(0, 99.0)             # permanent window
    assert inj.device_fault_end(0, 99.0) == INF


# ---------------------------------------------------------------------------
# 3. datapath abort units
# ---------------------------------------------------------------------------


def _dp(bw=1 * GB):
    mem = DeviceMemoryManager(32 * GB, policy="prefetch_swap")
    dp = DeviceDataPath(0, bw, 64 * GB, mem)
    mem.uploader = dp.request
    mem.residency_listeners.append(dp.on_residency)
    return mem, dp


def test_abort_with_retry_restarts_from_byte_zero_keeping_waiters():
    mem, dp = _dp()
    got = []
    dp.request("f", 2 * GB, 0.0, kind="demand")
    dp.transfers["f"].waiters.append(got.append)
    dp.link.pop_completed(1.0)          # 1 GB moved
    assert dp.transfers["f"].remaining == pytest.approx(1 * GB)
    assert dp.abort("f", 1.0, retry=True)
    t = dp.transfers["f"]
    assert t.remaining == pytest.approx(2 * GB)     # progress lost
    assert t.waiters == [got.append]                # waiter preserved
    assert dp.transfer_aborts == 1
    done = dp.advance(3.0)              # 2 more GB: lands at t=3
    assert [x.fn_id for x in done] == ["f"] and got == [3.0]


def test_abort_without_retry_fails_waiters_and_drops_the_region():
    mem, dp = _dp()
    got = []
    dp.request("f", 2 * GB, 0.0, kind="demand")
    dp.transfers["f"].waiters.append(got.append)
    assert dp.abort("f", 0.5, retry=False)
    assert got == [None]                # executor fails the attempt
    assert "f" not in dp.transfers
    assert dp.staging.used == 0
    assert not dp.abort("f", 0.6)       # idempotent: nothing left


def test_abort_all_tears_down_without_firing_waiters():
    mem, dp = _dp()
    got = []
    dp.request("a", 1 * GB, 0.0, kind="demand")
    dp.transfers["a"].waiters.append(got.append)
    mem.begin_prefetch("b", 1 * GB, 0.0)
    assert dp.abort_all(1.0) == 2
    assert not dp.transfers and dp.n_prefetch == 0
    assert dp.staging.used == 0
    assert got == []                    # control plane fails the inv itself


# ---------------------------------------------------------------------------
# 4. sim: every fault class conserves work
# ---------------------------------------------------------------------------


def test_sim_endpoint_faults_retry_to_completion():
    plan = FaultPlan(endpoint_faults=(EndpointFault("f0", 1, "error"),
                                      EndpointFault("f1", 0, "hang", 0.02),
                                      EndpointFault("f2", 2, "error")))
    srv = make_server(_sim_cfg(faults=plan), fns=_fns())
    rr = srv.run_trace(_trace(80, 0.01))
    f = rr.faults
    _zero_stranded(rr)
    assert f.endpoint_faults == 3
    assert f.attempts_failed == 3 and f.retries == 3 and f.requeued == 3
    assert f.completed_ok == 80 and f.dropped == 0
    assert rr.goodput() == 1.0
    assert sum(i.retries for i in rr.invocations) == 3


def test_sim_transient_device_fault_requeues_and_readmits():
    plan = FaultPlan(device_faults=(DeviceFault(0.5, 0, 1.0),))
    srv = make_server(_sim_cfg(faults=plan, quarantine_s=0.5),
                      fns=_fns(warm=0.2))
    rr = srv.run_trace(_trace(60, 0.05))
    f = rr.faults
    _zero_stranded(rr)
    assert f.device_faults == 1
    assert f.quarantined == 1 and f.readmitted == 1
    assert f.completed_ok == 60         # everything retried to completion
    # the doomed in-flight attempts were re-charged, not double-charged:
    # each retried invocation completed exactly once
    ids = [i.inv_id for i in rr.invocations if i.done]
    assert len(ids) == len(set(ids)) == 60
    # work kept flowing during the outage on the surviving device
    assert any(i.device_id == 1 for i in rr.invocations)


def test_sim_permanent_device_fault_never_readmits():
    plan = FaultPlan(device_faults=(DeviceFault(0.5, 0, INF),))
    srv = make_server(_sim_cfg(faults=plan), fns=_fns(warm=0.1))
    rr = srv.run_trace(_trace(60, 0.05))
    f = rr.faults
    _zero_stranded(rr)
    assert f.quarantined == 1 and f.readmitted == 0
    assert f.completed_ok == 60
    # after the fault, nothing is placed on the dead device
    t_fault = 0.5
    late = [i for i in rr.invocations if i.exec_start is not None
            and i.exec_start > t_fault + 0.2]
    assert late and all(i.device_id == 1 for i in late)


def test_sim_transfer_fault_restarts_the_upload():
    """A 2 GB demand transfer at 1 GB/s is mid-flight at t=0.5; the
    abort restarts it from byte zero, so the cold start lands ~0.5 s
    later than fault-free — but it lands."""
    plan = FaultPlan(transfer_faults=(TransferFault(0.5, 0, None),))
    fns = _fns(n=2, warm=0.05, mem=2 * GB, cold=3.0)
    cfg = _sim_cfg(n_devices=1, datapath="pipeline", h2d_bw=1 * GB,
                   faults=plan)
    rr = make_server(cfg, fns=fns).run_trace([TraceEvent(0.0, "f0")])
    f = rr.faults
    _zero_stranded(rr)
    assert f.transfer_aborts >= 1
    assert f.completed_ok == 1
    inv = rr.invocations[0]
    assert inv.done and not inv.failed
    assert inv.overhead > 2.0           # paid the restarted transfer


def test_sim_shedding_is_per_tenant_fair():
    plan = FaultPlan()                  # injector on, no faults: shed only
    fns = _fns(n=5, warm=0.2)
    trace = sorted([TraceEvent(0.001 * i, "f0") for i in range(100)]
                   + [TraceEvent(0.001 * i, f"f{1 + i % 4}")
                      for i in range(20)])
    srv = make_server(_sim_cfg(n_devices=1, faults=plan,
                               shed_threshold_s=0.5), fns=fns)
    rr = srv.run_trace(trace)
    f = rr.faults
    _zero_stranded(rr)
    assert f.shed > 0
    shed_fns = {i.fn_id for i in rr.invocations if i.shed}
    assert shed_fns == {"f0"}           # only the hog is rejected
    assert f.completed_ok + f.shed == f.arrivals


# ---------------------------------------------------------------------------
# 5. fault-free differential: empty plan == faults=None, bit for bit
# ---------------------------------------------------------------------------


def _completions(rr):
    return [(i.inv_id, i.exec_start, i.completion, i.device_id,
             i.start_type) for i in rr.invocations]


def test_empty_plan_is_bit_identical_to_no_plan():
    fns = _fns(warm=0.07, cold=0.3)
    trace = _trace(120, 0.013)
    base = make_server(_sim_cfg(), fns=fns).run_trace(trace)
    hooked = make_server(_sim_cfg(faults=FaultPlan()),
                         fns=fns).run_trace(trace)
    assert base.faults is None
    assert hooked.faults is not None
    assert _completions(base) == _completions(hooked)
    assert base.mean_latency() == hooked.mean_latency()


# ---------------------------------------------------------------------------
# 6. recovery-off reference: injected, unhandled, collapsed
# ---------------------------------------------------------------------------


def test_recovery_off_fails_fast_and_loses_goodput():
    plan = FaultPlan(
        device_faults=(DeviceFault(0.5, 0, INF),),
        endpoint_faults=(EndpointFault("f1", 0, "error"),))
    fns = _fns(warm=0.1)
    trace = _trace(60, 0.05)
    rr_on = make_server(_sim_cfg(faults=plan), fns=fns).run_trace(trace)
    rr_off = make_server(_sim_cfg(faults=plan, recovery=False),
                         fns=fns).run_trace(trace)
    _zero_stranded(rr_on)
    _zero_stranded(rr_off)
    f = rr_off.faults
    assert f.retries == 0 and f.quarantined == 0    # no reaction at all
    assert f.completed_failed > 0
    assert rr_off.goodput() < rr_on.goodput() == 1.0
    # failed attempts are excluded from the latency metrics
    assert rr_off.failed_count == f.completed_failed
    assert rr_off.mean_latency() > 0.0


# ---------------------------------------------------------------------------
# 7. wallclock
# ---------------------------------------------------------------------------


def _wall(fns, plan, *, recovery=True, delay=0.002, **kw):
    eps = {fn: StubEndpoint(fn, s, delay=delay) for fn, s in fns.items()}
    cfg = ServerConfig(executor="wallclock", n_devices=2, faults=plan,
                       recovery=recovery, sampling="transition",
                       batch_dispatch=True, device_layer="indexed", **kw)
    return make_server(cfg, fns=fns, endpoints=eps)


def test_wallclock_endpoint_fault_counters_match_sim():
    """The acceptance criterion: the same seeded (endpoint-only — the
    count trigger is the clock-independent one) plan produces matching
    fault/retry/shed counters under both executors."""
    plan = FaultPlan(endpoint_faults=(EndpointFault("f0", 2, "error"),
                                      EndpointFault("f1", 1, "hang", 0.01),
                                      EndpointFault("f2", 0, "error")))
    fns = _fns(warm=0.005)
    srv = _wall(fns, plan)
    srv.start()
    for i in range(40):
        srv.submit(f"f{i % 4}")
        time.sleep(0.002)
    srv.drain(timeout=30)
    rw = srv.stop()
    rs = make_server(_sim_cfg(faults=plan),
                     fns=fns).run_trace(_trace(40, 0.002))
    _zero_stranded(rw)
    _zero_stranded(rs)
    fw, fs = rw.faults, rs.faults
    for k in ("arrivals", "endpoint_faults", "attempts_failed",
              "retries", "requeued", "completed_ok", "dropped", "shed"):
        assert getattr(fw, k) == getattr(fs, k), k


def test_wallclock_device_fault_watchdog_recovers():
    plan = FaultPlan(device_faults=(DeviceFault(0.1, 0, 0.3),))
    srv = _wall(_fns(warm=0.01), plan, delay=0.01, quarantine_s=0.1)
    srv.start()
    # feed well past the readmission point (fault clears at t=0.4) so
    # the watchdog's health check runs while the server is still live
    for i in range(120):
        srv.submit(f"f{i % 4}")
        time.sleep(0.005)
    srv.drain(timeout=30)
    rr = srv.stop()
    f = rr.faults
    _zero_stranded(rr)
    assert f.device_faults == 1
    assert f.quarantined == 1 and f.readmitted == 1
    assert f.completed_ok + f.dropped == 120


def test_drain_timeout_tears_down_the_dispatcher():
    """Regression (satellite): ``drain`` used to raise ``TimeoutError``
    with the dispatcher (and workers) still running behind the caller's
    back. Now the stop event is signaled and the threads joined before
    the exception propagates."""
    fns = _fns(n=1)
    srv = _wall(fns, None, delay=1.5)
    ex = srv.executor
    srv.start()
    srv.submit("f0")                    # worker sleeps 1.5 s
    with pytest.raises(TimeoutError):
        srv.drain(timeout=0.1)
    assert ex._stop.is_set()
    assert not ex._dispatcher.is_alive()


# ---------------------------------------------------------------------------
# 8. sharded wallclock: vt_sync_errors surfaced
# ---------------------------------------------------------------------------


def test_vt_sync_error_is_counted_and_the_run_drains():
    fns = _fns(n=8, warm=0.002)
    eps = {fn: StubEndpoint(fn, s, delay=0.002) for fn, s in fns.items()}
    cfg = ServerConfig(executor="wallclock", sharding="hash", n_shards=2,
                       n_devices=2, vt_epoch=0.02)
    srv = make_server(cfg, fns=fns, endpoints=eps)
    ex = srv.executor
    inner = ex.sync_vt_once
    state = {"boomed": False}

    def flaky():
        if not state["boomed"]:
            state["boomed"] = True
            raise RuntimeError("injected epoch failure")
        inner()

    ex.sync_vt_once = flaky
    srv.start()
    for i in range(120):
        srv.submit(f"f{i % 8}")
    srv.drain(timeout=60)
    rr = srv.stop()
    assert rr.vt_sync_errors >= 1       # surfaced in RunResult
    assert srv.control.vt_sync_errors >= 1
    assert rr.completed_count == 120    # the run survived the failure
    assert srv.control.vt_syncs >= 1    # and the sync kept going


# ---------------------------------------------------------------------------
# 9. replay: feeder faults + loud worker-error propagation
# ---------------------------------------------------------------------------


def test_feeder_outage_is_counted_and_slips_lateness():
    from repro.replay import replay_open_loop
    from repro.workloads.scenarios import make_scenario
    sc = make_scenario("azure-longtail", n_fns=6, max_events=200)
    sc.faults = FaultPlan(feeder_faults=(FeederFault(2.0, 0, 20.0),))
    eps = {fn: StubEndpoint(fn, s, delay=0.001)
           for fn, s in sc.fns.items()}
    cfg = ServerConfig(executor="wallclock", n_devices=2,
                       faults=sc.faults, sampling="transition",
                       batch_dispatch=True, device_layer="indexed")
    srv = make_server(cfg, endpoints=eps, fns=sc.fns)
    rr = replay_open_loop(srv, sc, speedup=300.0, drain_timeout=60)
    assert rr.result.faults.feeder_kills == 1
    assert rr.released == rr.result.completed_count
    # the 20 trace-second outage shows up as feed-side slip, not as
    # server queueing: at 300x that is ~66 ms of wall lateness
    assert rr.max_lateness > 0.03


def test_feeder_worker_error_propagates_with_context():
    """Regression (satellite): a feeder whose submit raises used to die
    silently, the replay 'completing' with a fraction of the trace."""
    from repro.replay import replay_open_loop
    from repro.workloads.scenarios import make_scenario
    sc = make_scenario("azure-longtail", n_fns=4, max_events=500)
    eps = {fn: StubEndpoint(fn, s, delay=0.001)
           for fn, s in sc.fns.items()}
    cfg = ServerConfig(executor="wallclock", n_devices=2)
    srv = make_server(cfg, endpoints=eps, fns=sc.fns)
    ex = srv.executor
    real_submit = ex.submit
    calls = {"n": 0}

    def exploding(fn_id, request=None):
        calls["n"] += 1
        if calls["n"] > 10:
            raise ValueError("backend connection lost")
        return real_submit(fn_id, request)

    ex.submit = exploding
    with pytest.raises(RuntimeError, match="feeder .* failed after "
                                           "releasing 10 arrivals") as e:
        replay_open_loop(srv, sc, speedup=10000.0, drain_timeout=10)
    assert isinstance(e.value.__cause__, ValueError)    # original kept
    assert not ex._dispatcher.is_alive()                # server stopped


# ---------------------------------------------------------------------------
# 10. chaos scenarios + validation
# ---------------------------------------------------------------------------


def test_chaos_scenario_end_to_end_conserves():
    cfg = _sim_cfg(n_devices=4, scenario="chaos-azure-longtail",
                   scenario_kwargs={"n_fns": 20, "max_events": 1500,
                                    "n_devices": 4, "device_faults": 2,
                                    "endpoint_fault_frac": 0.4})
    rr = make_server(cfg).run_scenario()
    f = rr.faults
    _zero_stranded(rr)
    assert f.device_faults >= 1
    assert rr.goodput() >= 0.95
    # same seed, same chaos: the scenario's plan is deterministic
    rr2 = make_server(cfg).run_scenario()
    assert rr2.faults == f


def test_fault_plan_device_ids_validated_against_fleet():
    plan = FaultPlan(device_faults=(DeviceFault(1.0, 7),))
    with pytest.raises(ValueError, match="device ids .7."):
        make_server(_sim_cfg(n_devices=2, faults=plan), fns=_fns())


def test_faults_require_the_fast_event_loop():
    with pytest.raises(ValueError, match="fast event loop"):
        make_server(_sim_cfg(sampling="per_event",
                             faults=FaultPlan()), fns=_fns())


def test_transfer_faults_require_the_pipeline_datapath():
    plan = FaultPlan(transfer_faults=(TransferFault(1.0, 0),))
    with pytest.raises(ValueError, match="pipeline"):
        make_server(_sim_cfg(faults=plan), fns=_fns())
