"""Cold-start data plane (repro.datapath): staged cold starts, the
contended per-device H2D link, the pinned-host staging pool, and
anticipatory weight prefetch through the memory manager's accounting.

Layered like the subsystem itself:

  1. stage decomposition + the cost-model parameter threading
  2. SharedLink share arithmetic (demand PS, prio-ordered prefetch,
     demand preemption)
  3. StagingPool bounds
  4. DeviceDataPath + DeviceMemoryManager wiring (upgrade, cancel,
     eviction-cancels-prefetch, staging preemption, admission safety)
  5. control-plane hooks (Inactive cancellation)
  6. end-to-end sim invariants + the scalar differential reference
  7. config validation
"""
import math

import pytest

from repro.datapath import (ColdStartStages, DeviceDataPath, SharedLink,
                            Transfer, stages_for)
from repro.memory.manager import GB, DeviceMemoryManager
from repro.memory.pool import StagingPool
from repro.server import ServerConfig, make_server
from repro.workloads.costmodel import COMPILE_TIME, H2D_BW, endpoint_spec
from repro.workloads.spec import DEFAULT_MIX, FunctionSpec, function_copies
from repro.workloads.traces import azure_trace

INF = float("inf")


# ---------------------------------------------------------------------------
# 1. cold-start stages + cost-model threading
# ---------------------------------------------------------------------------


def test_stages_scalar_cold_init_is_the_uncontended_sum():
    st = ColdStartStages(setup_s=0.5, compile_s=2.0, weight_bytes=8 * GB)
    assert st.fixed_s == 2.5
    assert st.scalar_cold_init(16 * GB) == 2.5 + 0.5


def test_stages_for_decomposes_a_scalar_spec():
    """Specs without explicit stages split cold_init into transfer (at
    the given bandwidth) + fixed, 30/70 setup/compile."""
    spec = FunctionSpec("f", warm_time=1.0, cold_init=3.0,
                        mem_bytes=16 * GB)
    st = stages_for(spec, 16 * GB)
    assert st.weight_bytes == 16 * GB
    fixed = 3.0 - 1.0                       # cold_init - transfer
    assert math.isclose(st.setup_s, 0.3 * fixed)
    assert math.isclose(st.compile_s, 0.7 * fixed)
    assert math.isclose(st.scalar_cold_init(16 * GB), spec.cold_init)
    # transfer longer than cold_init: fixed clamps at zero
    st2 = stages_for(FunctionSpec("g", warm_time=1.0, cold_init=0.5,
                                  mem_bytes=16 * GB), 16 * GB)
    assert st2.fixed_s == 0.0


def test_stages_for_prefers_explicit_stages():
    st = ColdStartStages(0.1, 0.2, 123)
    spec = FunctionSpec("f", warm_time=1.0, cold_init=9.0, mem_bytes=456,
                        stages=st)
    assert stages_for(spec, 1e9) is st


def test_endpoint_spec_threads_cost_parameters():
    base = endpoint_spec("chatglm3-6b", "decode_32k")
    wbytes = base.stages.weight_bytes
    # defaults reproduce the historical scalar: COMPILE_TIME + upload
    assert math.isclose(base.cold_init, COMPILE_TIME + wbytes / H2D_BW)
    tuned = endpoint_spec("chatglm3-6b", "decode_32k", compile_time=2.0,
                          h2d_bw=16 * GB, setup_time=0.5)
    assert tuned.stages == ColdStartStages(0.5, 2.0, wbytes)
    assert math.isclose(tuned.cold_init, 2.5 + wbytes / (16 * GB))


# ---------------------------------------------------------------------------
# 2. SharedLink
# ---------------------------------------------------------------------------


def test_demand_transfers_split_the_link_equally():
    ln = SharedLink(10.0)
    a, b = Transfer("a", 100, "demand"), Transfer("b", 100, "demand")
    ln.add(a, 0.0)
    ln.add(b, 0.0)
    assert a.eta == b.eta == 20.0           # 100 / (10/2)
    done = ln.pop_completed(10.0)           # halfway: 50 bytes each
    assert done == [] and math.isclose(a.remaining, 50.0)
    ln.remove(b, 10.0)                      # b's dispatch aborted
    assert math.isclose(a.eta, 15.0)        # full bandwidth again
    assert ln.pop_completed(15.0) == [a]
    assert ln.next_eta() is None


def test_prefetch_is_served_one_at_a_time_in_prio_order():
    ln = SharedLink(10.0)
    a = Transfer("a", 100, "prefetch", prio=2)
    b = Transfer("b", 50, "prefetch", prio=1)
    ln.add(a, 0.0)
    ln.add(b, 0.0)
    # b (lower prio value) streams at full bandwidth; a waits
    assert b.eta == 5.0 and a.eta == INF
    assert ln.next_eta() == 5.0
    assert ln.pop_completed(5.0) == [b]
    assert a.eta == 15.0                    # untouched bytes, full bw
    assert math.isclose(a.remaining, 100.0)


def test_demand_preempts_prefetch_and_progress_is_kept():
    ln = SharedLink(10.0)
    p = Transfer("p", 100, "prefetch")
    ln.add(p, 0.0)
    assert p.eta == 10.0
    d = Transfer("d", 40, "demand")
    ln.add(d, 2.0)                          # p has moved 20 bytes
    assert d.eta == 6.0 and p.eta == INF    # p paused, d at full bw
    assert ln.pop_completed(6.0) == [d]
    assert math.isclose(p.remaining, 80.0)  # nothing lost while paused
    assert math.isclose(p.eta, 14.0)


def test_upgraded_prefetch_joins_the_demand_class():
    ln = SharedLink(10.0)
    p = Transfer("p", 100, "prefetch")
    d = Transfer("d", 100, "demand")
    ln.add(p, 0.0)
    ln.add(d, 0.0)                          # p paused from the start
    ln.mark_demand(p, 5.0)                  # d has moved 50
    assert math.isclose(p.eta, 25.0)        # 100 bytes at bw/2
    assert math.isclose(d.eta, 15.0)        # 50 left at bw/2


# ---------------------------------------------------------------------------
# 3. StagingPool
# ---------------------------------------------------------------------------


def test_staging_pool_bounds_and_oversize():
    sp = StagingPool(10)
    assert sp.reserve(6) and sp.used == 6
    assert not sp.reserve(6)                # would exceed
    assert sp.rejections == 1
    sp.release(6)
    assert sp.used == 0
    # oversize request admitted only when the pool is empty (chunked
    # streaming in reality; refusing forever would deadlock)
    assert sp.reserve(25)
    assert not sp.reserve(1)
    sp.release(25)
    assert sp.used == 0 and sp.peak == 25


# ---------------------------------------------------------------------------
# 4. DeviceDataPath + DeviceMemoryManager
# ---------------------------------------------------------------------------


def _wired(capacity=32 * GB, bw=1 * GB, staging=64 * GB):
    mem = DeviceMemoryManager(capacity, policy="prefetch_swap")
    dp = DeviceDataPath(0, bw, staging, mem)
    mem.uploader = dp.request
    mem.residency_listeners.append(dp.on_residency)
    return mem, dp


def test_begin_prefetch_then_dispatch_upgrade():
    mem, dp = _wired()
    assert mem.begin_prefetch("f", 4 * GB, 0.0)
    assert "f" in dp.transfers and dp.n_prefetch == 1
    assert not mem.is_resident("f", 1.0)    # in flight, not usable
    # dispatch at t=1: acquire sees the in-flight region; the executor
    # upgrades the transfer to demand
    ready, mult = mem.acquire("f", 4 * GB, 1.0)
    assert mult == 1.0 and ready == 4.0     # plan unchanged: sole transfer
    dp.mark_demand("f", 1.0)
    assert dp.transfers["f"].kind == "demand"
    done = dp.advance(4.0)
    assert [t.fn_id for t in done] == ["f"]
    assert mem.is_resident("f", 4.0)
    assert dp.staging.used == 0
    assert (dp.prefetches_started, dp.prefetches_upgraded,
            dp.transfers_completed) == (1, 1, 1)


def test_cancel_refuses_demand_and_waited_transfers():
    mem, dp = _wired()
    dp.request("d", GB, 0.0, kind="demand")
    assert not dp.cancel("d", 0.0)          # an invocation waits on it
    mem.begin_prefetch("p", GB, 0.0)
    dp.transfers["p"].waiters.append(lambda t: None)
    assert not dp.cancel("p", 0.0)          # waiter pinned
    mem.begin_prefetch("q", GB, 0.0)
    assert dp.cancel("q", 0.0)
    assert "q" not in dp.transfers and dp.prefetches_cancelled == 1
    assert dp.staging.used == 2 * GB        # d + p still staged


def test_eviction_of_inflight_prefetch_cancels_its_transfer():
    """A dispatching flow reclaims a prefetch-in-flight region: the
    evict listener aborts the transfer and releases its staging."""
    mem, dp = _wired(capacity=10 * GB)
    assert mem.begin_prefetch("bg", 6 * GB, 0.0)
    # the prefetched region is charged but stays evictable mid-flight
    assert mem.regions["bg"].evictable
    ready, _ = mem.acquire("hot", 8 * GB, 1.0)   # needs bg's 6 GB back
    assert mem.is_resident("hot", ready)
    assert "bg" not in dp.transfers and dp.prefetches_cancelled == 1
    assert not mem.regions["bg"].resident
    assert dp.staging.used == 8 * GB             # only hot's buffer


def test_prefetch_never_causes_admission_failure():
    """Admission is computed over *running* working sets; a background
    prefetch charges capacity but never running_bytes, so a dispatching
    flow admits exactly as it would without the prefetch — the prefetch
    is what yields (evicted + cancelled), not the dispatch."""
    mem, dp = _wired(capacity=10 * GB)
    assert mem.begin_prefetch("bg", 6 * GB, 0.0)
    running_bytes = 0                            # nothing dispatched yet
    assert mem.admit("hot", 8 * GB, running_bytes, 1.0)
    ready, _ = mem.acquire("hot", 8 * GB, 1.0)
    assert ready < INF and mem.is_resident("hot", ready)


def test_demand_preempts_staged_prefetch_buffers():
    """Staging full of idle prefetch buffers must not block a dispatch:
    the demand transfer bumps paused prefetches (worst prio first) off
    the pool and they re-queue with their progress intact."""
    mem, dp = _wired(staging=10 * GB, capacity=64 * GB)
    mem.uploader = None                          # drive dp directly
    dp.request("p1", 4 * GB, 0.0, kind="prefetch", prio=1)
    dp.request("p2", 4 * GB, 0.0, kind="prefetch", prio=2)
    assert dp.staging.used == 8 * GB
    dp.request("d", 6 * GB, 1.0, kind="demand")
    d = dp.transfers["d"]
    assert not d.queued and d.eta < INF          # p2 was bumped for it
    p2 = dp.transfers["p2"]
    assert p2.queued and dp.transfers["p1"].queued is False
    assert dp.staging.used == 10 * GB            # p1 + d
    # completion drains the pool and restages the bumped prefetch
    dp.advance(d.eta)
    assert not p2.queued and dp.staging.used == 8 * GB


# ---------------------------------------------------------------------------
# 5. control-plane hooks
# ---------------------------------------------------------------------------


def _pipeline_server(prefetch=True, **kw):
    fns = kw.pop("fns", None) or function_copies(DEFAULT_MIX, 8)
    cfg = ServerConfig(policy="mqfq-sticky",
                       policy_kwargs={"T": 5.0, "alpha": 0.5},
                       datapath="pipeline", prefetch=prefetch,
                       h2d_bw=1 * GB, **kw)
    return make_server(cfg, fns=fns)


def test_inactive_transition_cancels_background_prefetch():
    from repro.core.flow import QueueState
    from repro.runtime.invocation import Invocation

    srv = _pipeline_server()
    cp = srv.control
    fn = next(iter(cp.fns))
    for dev in cp.devices:      # isolate the *background* prefetch path
        dev.mem.anticipatory_upload = False
    cp.on_arrival(Invocation(fn, 0.0, 0), 0.0)
    q = cp.policy.queues[fn]
    dev = cp._fn_device(fn)
    assert dev.mem.begin_prefetch(fn, cp.fns[fn].mem_bytes, 0.0)
    assert fn in dev.datapath.transfers
    # the anticipation lapses: Active -> Inactive aborts the transfer
    # and releases the region through the eviction path
    cp._on_state_change(q, QueueState.ACTIVE, QueueState.INACTIVE, 5.0)
    assert fn not in dev.datapath.transfers
    assert dev.datapath.prefetches_cancelled == 1
    assert not dev.mem.regions[fn].resident
    assert dev.datapath.staging.used == 0


# ---------------------------------------------------------------------------
# 6. end-to-end sim runs
# ---------------------------------------------------------------------------


def _storm_kwargs(**over):
    kw = dict(n_fns=20, duration=720.0, wave_period=180.0, wave_width=4.0,
              participation=0.9, seed=3, spec_profile="llm",
              llm_h2d_bw=16 * GB)
    kw.update(over)
    return kw


def _storm_run(prefetch):
    cfg = ServerConfig(policy="mqfq-sticky",
                       policy_kwargs={"T": 10.0, "alpha": 0.3},
                       d=1, n_devices=1, capacity_bytes=512 * GB,
                       h2d_bw=16 * GB, pool_size=64,
                       datapath="pipeline", prefetch=prefetch,
                       scenario="cold-start-storm",
                       scenario_kwargs=_storm_kwargs())
    srv = make_server(cfg)
    return srv.run_scenario(), srv


def test_pipeline_storm_invariants_and_prefetch_win():
    res_base, srv_base = _storm_run(prefetch=False)
    res_pref, srv_pref = _storm_run(prefetch=True)
    assert res_pref.completed_count == res_base.completed_count > 0
    for srv in (srv_base, srv_pref):
        for dev in srv.control.devices:
            dp = dev.datapath
            assert not dp.transfers          # every transfer drained
            assert dp.staging.used == 0      # every buffer released
            assert dp.transfers_completed == (dp.demand_transfers
                                              + dp.prefetches_started
                                              - dp.prefetches_cancelled)
    dp = srv_pref.control.devices[0].datapath
    assert dp.prefetches_started > 0
    # prefetch converts GPU-cold starts into warm starts and shrinks
    # the total cold-start overhead actually paid
    warm = res_pref.start_type_counts().get("warm", 0)
    assert warm > res_base.start_type_counts().get("warm", 0)
    paid_base = sum(i.overhead for i in res_base.invocations)
    paid_pref = sum(i.overhead for i in res_pref.invocations)
    assert paid_pref < paid_base


def test_keep_alive_baseline_never_uploads_before_dispatch():
    res, srv = _storm_run(prefetch=False)
    for dev in srv.control.devices:
        dp = dev.datapath
        assert dp.prefetches_started == 0
        assert dp.demand_transfers == dp.transfers_completed


def test_pipeline_cold_overhead_never_below_fixed_stages():
    """Staged cold starts pay at least setup+compile even when the
    transfer is fully hidden (the overlap can't hide the fixed part)."""
    res, srv = _storm_run(prefetch=True)
    fixed = 0.3 + 1.2                        # the llm profile's stages
    for i in res.invocations:
        if i.start_type == "cold":
            assert i.overhead >= fixed - 1e-9


def test_scalar_datapath_is_bit_identical_to_the_pre_pr_stack():
    """datapath='scalar' must leave the whole plane byte-for-byte on the
    seed semantics: the full pre-PR reference stack (reference device
    layer + per-token dispatch + per-event sampling) replays the same
    pressured trace to the same dispatch/state/eviction streams and
    metrics."""
    fns = function_copies(DEFAULT_MIX, 12)
    trace = azure_trace(fns, duration=150.0, trace_id=3)
    pressure = dict(d=2, n_devices=2, capacity_bytes=3 * GB, pool_size=8,
                    policy="mqfq-sticky", policy_kwargs={"T": 5.0},
                    strict_reclaim=True)

    def replay(**kw):
        srv = make_server(ServerConfig(**kw), fns=fns)
        dispatches, states, evicts = [], [], []
        srv.bus.on_dispatch(lambda ev: dispatches.append(
            (ev.inv.inv_id, ev.fn_id, ev.device_id, ev.start_type,
             ev.time)))
        srv.bus.on_state_change(lambda ev: states.append(
            (ev.fn_id, ev.old.value, ev.new.value, ev.time)))
        for dev in srv.control.devices:
            dev.mem.residency_listeners.append(
                lambda fn, res, i=dev.dev_id: res or evicts.append((i, fn)))
        res = srv.run_trace(trace)
        summary = (len(res.invocations), res.mean_latency(),
                   res.p99_latency(), res.start_type_counts(),
                   res.mean_utilization())
        return dispatches, states, evicts, summary

    scalar = replay(datapath="scalar")
    seed = replay(device_layer="reference", batch_dispatch=False,
                  sampling="per_event")
    assert scalar == seed


# ---------------------------------------------------------------------------
# 7. config validation
# ---------------------------------------------------------------------------


def test_pipeline_config_validation():
    fns = function_copies(DEFAULT_MIX, 2)
    with pytest.raises(ValueError, match="datapath"):
        make_server(ServerConfig(datapath="turbo"), fns=fns)
    with pytest.raises(ValueError, match="sim-only"):
        make_server(ServerConfig(datapath="pipeline",
                                 executor="wallclock"), endpoints={})
    with pytest.raises(ValueError, match="fast event loop"):
        make_server(ServerConfig(datapath="pipeline",
                                 sampling="per_event"), fns=fns)
    with pytest.raises(ValueError, match="fast event loop"):
        make_server(ServerConfig(datapath="pipeline",
                                 batch_dispatch=False), fns=fns)
    with pytest.raises(ValueError, match="prefetch"):
        make_server(ServerConfig(prefetch=True), fns=fns)
    with pytest.raises(ValueError, match="indexed"):
        make_server(ServerConfig(datapath="pipeline",
                                 device_layer="reference"), fns=fns)
