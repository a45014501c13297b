"""Data plane v2 (repro.datapath): the peer-to-peer transfer fabric,
chunked layer streaming, time-to-resident placement, and the cached
SharedLink hot path.

Layered like the subsystem:

  1. SharedLink v2 surface: chunk milestones, backlog, cached next_eta
  2. Fabric: directed links, sourcing index
  3. DeviceDataPath peer migration: streaming, fallback, cancel, faults
  4. chunked streaming through the DeviceDataPath + executor
  5. time-to-resident placement bids
  6. end-to-end sim runs (migration win, chunk win, storm invariants,
     chaos quarantine mid-migration drains clean)
  7. differential reference: cached link vs ReferenceSharedLink across
     policies x memory pressure (bit-identical), defaults ≡ PR-6 plane
  8. conservation fuzz (seeded always-run + hypothesis-gated)
  9. the TRANSFER-timer re-arm regression (paused prefetch unpauses on
     the demand completion, sim executor; wallclock has no pipeline)
 10. config validation for the new knobs
"""
import math
import random

import pytest

from repro.datapath import (ColdStartStages, DeviceDataPath, Fabric,
                            ReferenceSharedLink, SharedLink, Transfer)
from repro.datapath.link import _EPS_BYTES
from repro.memory.manager import GB, DeviceMemoryManager
from repro.server import ServerConfig, make_server
from repro.workloads.spec import DEFAULT_MIX, FunctionSpec, function_copies
from repro.workloads.traces import TraceEvent

INF = float("inf")


# ---------------------------------------------------------------------------
# 1. SharedLink v2 surface
# ---------------------------------------------------------------------------


def test_chunk_milestone_eta_and_pop():
    ln = SharedLink(10.0)
    t = Transfer("f", 100, "demand")
    ln.add(t, 0.0)
    ln.arm_milestone(t, 60.0, 0.0)          # fire once 40 bytes landed
    assert t.chunk_eta == 4.0 and t.eta == 10.0
    assert ln.next_eta() == 4.0             # milestone is the next event
    assert ln.pop_milestones(3.0) == []     # not yet
    hit = ln.pop_milestones(4.0)
    assert hit == [t] and t.chunk_rem is None and t.chunk_eta == INF
    assert ln.next_eta() == 10.0            # back to the completion
    assert ln.pop_completed(10.0) == [t]


def test_chunk_milestone_pauses_with_its_transfer():
    ln = SharedLink(10.0)
    p = Transfer("p", 100, "prefetch")
    ln.add(p, 0.0)
    ln.arm_milestone(p, 50.0, 0.0)
    assert p.chunk_eta == 5.0
    d = Transfer("d", 40, "demand")
    ln.add(d, 0.0)                          # p pauses, milestone too
    assert p.eta == INF and p.chunk_eta == INF
    assert ln.next_eta() == 4.0             # d's completion
    ln.pop_completed(4.0)
    assert math.isclose(p.chunk_eta, 9.0)   # unpaused: 50 more bytes


def test_milestone_and_completion_can_coincide():
    """A milestone armed at (or integrated past) zero remaining is
    consumed by pop_completed, not left dangling."""
    ln = SharedLink(10.0)
    t = Transfer("f", 100, "demand")
    ln.add(t, 0.0)
    ln.arm_milestone(t, 10.0, 0.0)
    done = ln.pop_completed(10.0)           # skipped the milestone pop
    assert done == [t] and t.chunk_rem is None
    assert ln.pop_milestones(11.0) == []
    assert ln.next_eta() is None


def test_backlog_counts_demand_bytes_only():
    ln = SharedLink(10.0)
    ln.add(Transfer("d", 100, "demand"), 0.0)
    ln.add(Transfer("p", 50, "prefetch"), 0.0)
    assert ln.backlog_bytes() == 100.0
    ln.pop_completed(5.0)                   # 50 demand bytes moved
    assert math.isclose(ln.backlog_bytes(), 50.0)


# ---------------------------------------------------------------------------
# 2. Fabric
# ---------------------------------------------------------------------------


def test_fabric_links_are_directed_and_lazy():
    f = Fabric(8.0)
    assert f.links == {} and f.backlog_bytes(0, 1) == 0.0
    l01 = f.link(0, 1)
    assert f.link(0, 1) is l01
    assert f.link(1, 0) is not l01          # directions are independent
    assert l01.bw == 8.0


def test_fabric_sourcing_index_round_trip():
    f = Fabric(8.0)
    dp_a, dp_b = object(), object()
    f.register(0, "f", dp_a)
    f.register(0, "f", dp_b)
    f.register(0, "g", dp_a)
    assert sorted(fn for fn, _ in f.sourcing_from(0)) == ["f", "f", "g"]
    f.unregister(0, "f", dp_b)
    assert set(f.on_source_evicted(0, "f")) == {dp_a}
    assert f.on_source_evicted(0, "f") == []        # consumed
    assert f.sourcing_from(0) == [("g", dp_a)]
    assert f.sourcing_from(3) == []


# ---------------------------------------------------------------------------
# 3. DeviceDataPath peer migration
# ---------------------------------------------------------------------------


def _fabric_wired(n=2, capacity=32 * GB, bw=1 * GB, p2p=8 * GB,
                  staging=64 * GB):
    """n memory/datapath pairs over one fabric, with the control plane's
    uploader convention: a transfer sources from a peer whose copy is
    usable *now*, else from host DRAM."""
    fabric = Fabric(p2p)
    mems, dps = [], []
    for i in range(n):
        mem = DeviceMemoryManager(capacity, policy="prefetch_swap")
        dp = DeviceDataPath(i, bw, staging, mem, fabric=fabric)
        mem.residency_listeners.append(dp.on_residency)
        mems.append(mem)
        dps.append(dp)

    def _uploader_for(dp):
        def uploader(fn_id, nbytes, now, kind="demand"):
            src = next((j for j, m in enumerate(mems)
                        if j != dp.dev_id and m.is_resident(fn_id, now)),
                       None)
            return dp.request(fn_id, nbytes, now, kind=kind, src=src)
        return uploader

    for mem, dp in zip(mems, dps):
        mem.uploader = _uploader_for(dp)
    return fabric, mems, dps


def _make_resident(mem, fn, nbytes, now=0.0):
    """Install a finished copy without leaving a transfer on any link
    (the scalar-estimate path), so source devices start quiescent."""
    up, mem.uploader = mem.uploader, None
    try:
        mem.acquire(fn, nbytes, now)
    finally:
        mem.uploader = up
    mem.finish_upload(fn, now)
    assert mem.is_resident(fn, now)


def test_peer_migration_streams_over_the_fabric():
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired()
    _make_resident(m0, "f", 4 * GB)
    eta, mult = m1.acquire("f", 4 * GB, 0.0)
    assert (eta, mult) == (0.5, 1.0)        # 4 GB over the 8 GB/s link
    assert dp1.staging.used == 0            # HBM->HBM: no host staging
    assert fabric.migrations_started == 1
    assert dp1.next_eta() == 0.5            # inbound links are aggregated
    done = dp1.advance(0.5)
    assert [t.fn_id for t in done] == ["f"]
    assert m1.is_resident("f", 0.5)
    assert fabric.migrations_completed == 1
    assert fabric.bytes_migrated == 4 * GB
    assert dp1.migrations_in == dp1.migrations_completed == 1
    assert fabric.in_flight() == [] and not dp1.transfers


def test_migration_source_eviction_falls_back_to_host():
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired()
    _make_resident(m0, "f", 4 * GB)
    m1.acquire("f", 4 * GB, 0.0)
    t = dp1.transfers["f"]
    assert t.src == 0
    waited = []
    t.waiters.append(waited.append)
    dp1.advance(0.25)                       # 2 GB migrated so far
    assert math.isclose(t.remaining, 2 * GB)
    # the source region leaves dev0's HBM mid-stream: the control
    # plane's evict listener detaches every destination and each one
    # restarts on its host link from byte zero
    for dst in fabric.on_source_evicted(0, "f"):
        assert dst.peer_source_lost("f", 0.25)
    assert t.src is None and t.remaining == float(4 * GB)
    assert t in dp1.link.active and dp1.staging.used == 4 * GB
    assert math.isclose(t.eta, 0.25 + 4.0)  # restart at h2d_bw = 1 GB/s
    assert dp1.migrations_fallback == fabric.migrations_fallback == 1
    assert fabric.in_flight() == []         # nothing left on the fabric
    done = dp1.advance(4.25)
    assert done == [t] and waited == [4.25] # dispatch waiter preserved
    assert m1.is_resident("f", 4.25) and dp1.staging.used == 0


def test_peer_prefetch_cancel_unregisters_cleanly():
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired()
    _make_resident(m0, "f", 2 * GB)
    assert m1.begin_prefetch("f", 2 * GB, 0.0)
    assert dp1.transfers["f"].src == 0
    assert dp1.cancel("f", 0.1)
    assert fabric.in_flight() == []
    assert fabric.on_source_evicted(0, "f") == []   # index cleaned
    assert dp1.n_prefetch == 0


def test_abort_retries_peer_migration_on_the_same_link():
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired()
    _make_resident(m0, "f", 4 * GB)
    m1.acquire("f", 4 * GB, 0.0)
    t = dp1.transfers["f"]
    assert dp1.abort("f", 0.25, retry=True)
    assert t.src == 0 and t.remaining == float(4 * GB)
    assert math.isclose(t.eta, 0.25 + 0.5)  # byte zero, still on fabric
    assert len(fabric.sourcing_from(0)) == 1
    # recovery off: dropped, waiters failed, fabric released
    failed = []
    t.waiters.append(failed.append)
    assert dp1.abort("f", 0.3, retry=False)
    assert failed == [None] and not dp1.transfers
    assert fabric.in_flight() == [] and fabric.sourcing_from(0) == []


def test_abort_all_clears_inbound_peer_links():
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired()
    _make_resident(m0, "f", 2 * GB)
    m1.acquire("f", 2 * GB, 0.0)            # peer: f resident on dev0
    m1.acquire("g", 1 * GB, 0.0)            # host transfer alongside
    assert dp1.transfers["f"].src == 0
    assert dp1.transfers["g"].src is None
    assert dp1.abort_all(0.1) == 2
    assert not dp1.transfers and dp1.staging.used == 0
    assert fabric.in_flight() == [] and fabric.sourcing_from(0) == []


# ---------------------------------------------------------------------------
# 4. chunked layer streaming (DeviceDataPath surface)
# ---------------------------------------------------------------------------


def test_await_first_chunk_arms_and_fires():
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired()
    m0.acquire("f", 8 * GB, 0.0)
    fired = []
    assert dp0.await_first_chunk("f", 2 * GB, fired.append, 0.0)
    t = dp0.transfers["f"]
    assert t.chunk_rem == float(6 * GB)
    assert dp0.next_eta() == 2.0            # milestone: 2 GB at 1 GB/s
    dp0.advance(2.0)
    assert fired == [2.0] and t.chunk_waiters == []
    assert "f" in dp0.transfers             # residual keeps streaming
    assert not m0.is_resident("f", 2.0)     # usable only when complete
    dp0.advance(8.0)
    assert m0.is_resident("f", 8.0) and not dp0.transfers


def test_await_first_chunk_short_circuits_when_landed():
    fabric, mems, (dp0, dp1) = _fabric_wired()
    mems[0].acquire("f", 8 * GB, 0.0)
    dp0.advance(7.0)                        # 7 GB landed, 1 GB left
    assert not dp0.await_first_chunk("f", 2 * GB, lambda t: None, 7.0)


def test_await_first_chunk_small_transfer_waits_full_completion():
    fabric, mems, (dp0, dp1) = _fabric_wired()
    mems[0].acquire("f", 1 * GB, 0.0)
    fired = []
    assert dp0.await_first_chunk("f", 2 * GB, fired.append, 0.0)
    t = dp0.transfers["f"]
    assert t.chunk_rem is None and fired == []
    dp0.advance(1.0)
    assert fired == [1.0]                   # via the completion waiters


def test_chunk_waiters_pin_the_transfer_against_cancel():
    fabric, mems, (dp0, dp1) = _fabric_wired()
    mems[0].begin_prefetch("f", 8 * GB, 0.0)
    assert dp0.await_first_chunk("f", 2 * GB, lambda t: None, 0.0)
    assert not dp0.cancel("f", 0.1)         # a dispatch depends on it


def test_chunk_milestone_survives_host_fallback():
    """Milestone re-arms on the host link after a mid-migration source
    eviction: the chunk waiter still fires (later, from byte zero)."""
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired()
    _make_resident(m0, "f", 8 * GB)
    m1.acquire("f", 8 * GB, 0.0)
    assert dp1.transfers["f"].src == 0
    fired = []
    assert dp1.await_first_chunk("f", 2 * GB, fired.append, 0.0)
    for dst in fabric.on_source_evicted(0, "f"):
        dst.peer_source_lost("f", 0.1)
    t = dp1.transfers["f"]
    assert t.chunk_rem == float(6 * GB)     # still armed, host link now
    dp1.advance(2.1)                        # 2 GB at h2d 1 GB/s
    assert fired == [2.1]


# ---------------------------------------------------------------------------
# 5. time-to-resident placement
# ---------------------------------------------------------------------------


def _ttr_server(n_devices=3, **kw):
    fns = {"f": FunctionSpec("f", warm_time=1.0, cold_init=1.0,
                             mem_bytes=8 * GB),
           "g": FunctionSpec("g", warm_time=1.0, cold_init=1.0,
                             mem_bytes=8 * GB)}
    cfg = ServerConfig(policy="mqfq-sticky", d=1, n_devices=n_devices,
                       capacity_bytes=64 * GB, h2d_bw=16 * GB,
                       datapath="pipeline", p2p_bw=96 * GB,
                       placement="time-to-resident", **kw)
    return make_server(cfg, fns=fns)


def test_ttr_prefers_peer_capable_device_over_inflight_upload():
    """The case sticky gets wrong: a device mid-host-upload counts as
    'resident' to the sticky pick, beating a device that could migrate
    the weights from a finished peer copy in a fraction of the time."""
    srv = _ttr_server()
    cp = srv.control
    d0, d1, d2 = cp.devices
    # dev0: finished copy, but no free token -> cannot bid
    _make_resident(d0.mem, "f", 8 * GB)
    d0.tokens.acquire()
    # dev2: host upload in flight, eta 0.5 s
    d2.mem.acquire("f", 8 * GB, 0.0)
    assert cp.pick_device("f") is d2        # sticky: in-flight counts
    # ttr: dev1 can migrate from dev0 in 8/96 s, beating dev2's 0.5 s
    assert cp._pick == cp._pick_device_ttr
    assert cp._pick("f") is d1
    # once dev2's upload lands it bids 0 and wins
    d2.mem.finish_upload("f", 0.0)
    assert cp._pick("f") is d2


def test_ttr_resident_beats_everything_and_load_breaks_ties():
    srv = _ttr_server()
    cp = srv.control
    d0, d1, d2 = cp.devices
    _make_resident(d1.mem, "f", 8 * GB)
    assert cp._pick("f") is d1              # ready = 0
    # no copies anywhere: host estimates tie, load decides (first wins)
    d0.note_dispatch(1, "g", cp.fns["g"])
    assert cp._pick("g") is d1
    # failed devices never bid
    d1.failed = True
    assert cp._pick("g") is d2


def test_ttr_host_estimate_includes_link_backlog():
    srv = _ttr_server(n_devices=2)
    cp = srv.control
    d0, d1 = cp.devices
    # dev0's link is busy with 16 GB of demand traffic; dev1 idle
    d0.datapath.request("g", 16 * GB, 0.0, kind="demand")
    assert cp._pick("f") is d1


# ---------------------------------------------------------------------------
# 6. end-to-end sim runs
# ---------------------------------------------------------------------------


def _mig_fns():
    st = ColdStartStages(0.05, 0.1, 8 * GB)
    return {
        "f": FunctionSpec("f", warm_time=1.0,
                          cold_init=st.fixed_s + 0.5, mem_bytes=8 * GB,
                          stages=st),
        "g": FunctionSpec("g", warm_time=20.0, cold_init=0.5,
                          mem_bytes=1 * GB),
    }


def test_e2e_cold_start_migrates_from_peer_hbm():
    """f becomes resident on dev0; while dev0's token is pinned by a
    long-running g, a new f lands on dev1 and streams its weights over
    the fabric instead of host DRAM."""
    cfg = ServerConfig(policy="mqfq-sticky", d=1, n_devices=2,
                       capacity_bytes=64 * GB, h2d_bw=16 * GB,
                       datapath="pipeline", p2p_bw=96 * GB)
    srv = make_server(cfg, fns=_mig_fns())
    trace = [TraceEvent(0.0, "f"),          # dev0: host cold start
             TraceEvent(2.0, "g"),          # dev0 resident-free token
             TraceEvent(3.0, "f")]          # dev0 busy -> dev1 migrates
    res = srv.run_trace(trace)
    fab = srv.control.fabric
    assert fab.migrations_started == fab.migrations_completed == 1
    assert fab.bytes_migrated == 8 * GB
    f2 = [i for i in res.invocations if i.fn_id == "f"][1]
    assert f2.device_id == 1
    # peer stream: 8 GB / 96 GB/s ~ 0.083 s, far below the 0.5 s host
    # transfer (fixed stages dominate instead)
    assert f2.overhead < 0.3
    for dev in srv.control.devices:
        assert not dev.datapath.transfers
    assert fab.in_flight() == []


def test_e2e_chunked_streaming_starts_execution_early():
    """32 GB of weights at 16 GB/s is a 2 s transfer. Chunked at 2 GB,
    execution starts when the first 2 GB land (0.125 s, floored by the
    0.15 s fixed stages) and the residual streams under the running
    invocation — so a warm second dispatch at t=1.3 (tail still in
    flight) also starts immediately instead of waiting for it."""
    st = ColdStartStages(0.05, 0.1, 32 * GB)
    fns = {"f": FunctionSpec("f", warm_time=1.0,
                             cold_init=st.fixed_s + 2.0,
                             mem_bytes=32 * GB, stages=st)}
    base = dict(policy="mqfq-sticky", d=1, n_devices=1,
                capacity_bytes=64 * GB, h2d_bw=16 * GB,
                datapath="pipeline")
    trace = [TraceEvent(0.0, "f"), TraceEvent(1.3, "f")]
    r_full = make_server(ServerConfig(**base), fns=fns).run_trace(trace)
    r_chunk = make_server(ServerConfig(**base, chunk_bytes=2 * GB),
                          fns=fns).run_trace(trace)
    f1_full, f2_full = sorted(r_full.invocations, key=lambda i: i.arrival)
    f1_ch, f2_ch = sorted(r_chunk.invocations, key=lambda i: i.arrival)
    # unchunked: the cold start waits the whole 2 s transfer, and the
    # queued second invocation rides behind it (token frees at 3.0)
    assert math.isclose(f1_full.exec_start, 2.0)
    assert math.isclose(f2_full.exec_start, 3.0)
    # chunked: start at max(first-chunk 0.125 s, fixed stages 0.15 s);
    # the warm second dispatch at 1.3 ignores the in-flight tail
    assert math.isclose(f1_ch.exec_start, 0.15)
    assert math.isclose(f2_ch.exec_start, 1.3)
    assert f2_ch.start_type == "host_warm"  # container hit, tail in flight
    # the makespan win: 2.3 vs 4.0
    assert math.isclose(f2_ch.completion, 2.3)
    assert math.isclose(f2_full.completion, 4.0)


def _v2_storm(n_events=None, seed=7, **over):
    kw = dict(policy="mqfq-sticky", policy_kwargs={"T": 10.0, "alpha": 0.3},
              d=1, n_devices=4, capacity_bytes=24 * GB, h2d_bw=16 * GB,
              datapath="pipeline", prefetch=True, p2p_bw=96 * GB,
              chunk_bytes=1 * GB, placement="time-to-resident")
    kw.update(over)
    cfg = ServerConfig(scenario="cold-start-storm",
                       scenario_kwargs=dict(n_fns=60, duration=400.0,
                                            seed=seed, spec_profile="llm",
                                            max_events=n_events or 200_000),
                       **kw)
    srv = make_server(cfg)
    return srv.run_scenario(), srv


def test_v2_storm_migrates_and_drains_clean():
    res, srv = _v2_storm()
    cp = srv.control
    assert cp.fabric is not None and cp.fabric.migrations_started > 0
    assert cp.fabric.migrations_completed \
        + cp.fabric.migrations_fallback > 0
    for dev in cp.devices:
        dp = dev.datapath
        assert not dp.transfers and dp.staging.used == 0
        assert not dp.waiting
    assert cp.fabric.in_flight() == []
    assert res.completed_count > 0


@pytest.mark.slow
def test_chaos_device_quarantine_mid_migration_drains_clean():
    """Acceptance: a device fault while migrations stream to/from it
    (abort_all on inbound, invalidate_device -> host fallback on
    outbound) leaves zero stranded bytes and zero stranded
    invocations."""
    cfg = ServerConfig(
        policy="mqfq-sticky", policy_kwargs={"T": 10.0, "alpha": 0.3},
        d=1, n_devices=4, capacity_bytes=24 * GB, h2d_bw=16 * GB,
        datapath="pipeline", prefetch=True, p2p_bw=96 * GB,
        chunk_bytes=1 * GB, placement="time-to-resident",
        scenario="chaos-cold-start-storm",
        scenario_kwargs=dict(chaos_seed=11, horizon_s=400.0, n_devices=4,
                             device_faults=2, transfer_faults=6,
                             n_fns=60, duration=400.0, seed=7,
                             spec_profile="llm", max_events=200_000))
    srv = make_server(cfg)
    res = srv.run_scenario()
    cp = srv.control
    f = res.faults
    assert f.device_faults > 0
    for i in res.invocations:
        assert i.done or i.shed, i
    assert f.accounted == f.arrivals, (f.accounted, f.arrivals)
    for dev in cp.devices:
        dp = dev.datapath
        assert not dp.transfers and not dp.waiting
        assert dp.staging.used == 0
    assert cp.fabric.in_flight() == []
    for src in range(4):                    # sourcing index fully drained
        assert cp.fabric.sourcing_from(src) == []


# ---------------------------------------------------------------------------
# 7. differential reference: cached link vs scanning link
# ---------------------------------------------------------------------------


def _invocation_stream(res):
    return [(i.fn_id, i.arrival, i.exec_start, i.completion, i.overhead,
             i.device_id, i.start_type) for i in res.invocations]


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["mqfq-sticky", "mqfq"])
@pytest.mark.parametrize("capacity", [512 * GB, 24 * GB])
def test_cached_link_is_bit_identical_to_reference(monkeypatch, policy,
                                                   capacity):
    """The incremental caches must not change a single float: the same
    storm (with every v2 feature on, so milestones and fabric links are
    exercised) replays bit-identically with ReferenceSharedLink swapped
    in device- and fabric-wide — across policies x memory pressure."""
    import repro.datapath.device as device_mod

    def run():
        res, srv = _v2_storm(policy=policy, capacity_bytes=capacity)
        return _invocation_stream(res)

    fast = run()
    monkeypatch.setattr(device_mod, "SharedLink", ReferenceSharedLink)
    monkeypatch.setattr(Fabric, "link_cls", ReferenceSharedLink)
    assert run() == fast


def test_v2_defaults_are_the_pr6_plane():
    """p2p_bw=0 / chunk_bytes=None / placement='sticky' must leave the
    pipeline exactly on the PR-6 code paths: no fabric is even built,
    no milestone is ever armed, and the sticky pick stays bound."""
    cfg = ServerConfig(policy="mqfq-sticky", d=1, n_devices=4,
                       capacity_bytes=24 * GB, h2d_bw=16 * GB,
                       datapath="pipeline", prefetch=True,
                       scenario="cold-start-storm",
                       scenario_kwargs=dict(n_fns=40, duration=300.0,
                                            seed=5, spec_profile="llm",
                                            max_events=100_000))
    srv = make_server(cfg)
    cp = srv.control
    assert cp.fabric is None
    assert cp._pick == cp.pick_device
    srv.run_scenario()
    for dev in cp.devices:
        assert dev.datapath._in_links == {}
        assert dev.datapath.migrations_in == 0
        assert dev.datapath.link._n_miles == 0


# ---------------------------------------------------------------------------
# 8. conservation fuzz: SharedLink/Fabric under random programs
# ---------------------------------------------------------------------------


def _run_link_program(rng, steps=60, bw=10.0):
    """Drive a cached and a reference link through one random mutation
    program, checking conservation + equivalence at every step."""
    fast, ref = SharedLink(bw), ReferenceSharedLink(bw)
    pairs = {}                              # fn -> (fast_t, ref_t)
    now, t0, nid = 0.0, 0.0, 0
    total_bytes = 0
    completed_bytes = 0.0

    def check():
        ef, er = fast.next_eta(), ref.next_eta()
        assert ef == er, (ef, er)
        # ETAs never plan into the past of the last integration
        if ef is not None:
            assert ef >= fast._last - 1e-9
        for tf, tr in pairs.values():
            assert tf.remaining == tr.remaining
            assert tf.eta == tr.eta and tf.chunk_eta == tr.chunk_eta
        # conservation: bytes moved never exceed bw * elapsed
        moved = completed_bytes + sum(
            tf.nbytes - tf.remaining for tf, _ in pairs.values())
        assert moved <= bw * (now - t0) + 1e-6

    for _ in range(steps):
        now += rng.random()
        op = rng.choice("aaamrkcp")
        if op == "a":
            nb = rng.randint(1, 60)
            kind = rng.choice(["demand", "prefetch"])
            prio = rng.randint(0, 4)
            fn = f"f{nid}"
            nid += 1
            total_bytes += nb
            tf = Transfer(fn, nb, kind, prio)
            tr = Transfer(fn, nb, kind, prio)
            pairs[fn] = (tf, tr)
            fast.add(tf, now)
            ref.add(tr, now)
        elif op == "m":
            cands = [f for f, (t, _) in pairs.items()
                     if t.kind != "demand"]
            if cands:
                fn = rng.choice(cands)
                fast.mark_demand(pairs[fn][0], now)
                ref.mark_demand(pairs[fn][1], now)
        elif op == "r":
            if pairs:
                fn = rng.choice(sorted(pairs))
                tf, tr = pairs.pop(fn)
                completed_bytes += tf.nbytes - tf.remaining
                fast.remove(tf, now)
                ref.remove(tr, now)
        elif op == "k":                     # arm a chunk milestone
            cands = [f for f, (t, _) in pairs.items()
                     if t.chunk_rem is None and t.remaining > 1.0]
            if cands:
                fn = rng.choice(cands)
                tf, tr = pairs[fn]
                cr = rng.uniform(0.0, tf.remaining - 0.5)
                fast.arm_milestone(tf, cr, now)
                ref.arm_milestone(tr, cr, now)
        else:                               # pop milestones + completions
            hf = [t.fn_id for t in fast.pop_milestones(now)]
            hr = [t.fn_id for t in ref.pop_milestones(now)]
            assert hf == hr
            df = fast.pop_completed(now)
            dr = ref.pop_completed(now)
            assert [t.fn_id for t in df] == [t.fn_id for t in dr]
            for t in df:
                # no transfer completes with material bytes missing
                assert t.remaining <= _EPS_BYTES
                completed_bytes += t.nbytes - t.remaining
                del pairs[t.fn_id]
        check()
    # drain stepwise at the planned event times, the way the executor
    # does: ETAs must be monotone and everything must complete
    prev = now
    for _ in range(10_000):
        e = fast.next_eta()
        if e is None:
            break
        assert e == ref.next_eta()
        assert e >= prev - 1e-9             # never plans into the past
        prev = now = max(e, now)
        hf = [t.fn_id for t in fast.pop_milestones(now)]
        assert hf == [t.fn_id for t in ref.pop_milestones(now)]
        for t in fast.pop_completed(now):
            assert t.remaining <= _EPS_BYTES
            del pairs[t.fn_id]
        for t in ref.pop_completed(now):
            assert t.remaining <= _EPS_BYTES
    else:
        pytest.fail("link did not drain")
    assert pairs == {} and not fast.active and not ref.active


def test_link_conservation_fuzz_seeded():
    rng = random.Random(0xFAB)
    for _ in range(150):
        _run_link_program(random.Random(rng.getrandbits(64)))


def test_fabric_conservation_fuzz_seeded():
    """Same program, but through fabric-owned directed links: per-link
    conservation holds and in_flight() mirrors the union."""
    rng = random.Random(0xFAB2)
    fab = Fabric(10.0)
    for i, pair in enumerate([(0, 1), (1, 0), (0, 2)]):
        link = fab.link(*pair)
        _run_link_program(random.Random(rng.getrandbits(64)))
        t = Transfer(f"x{i}", 5, "demand")
        link.add(t, 0.0)
    assert len(fab.in_flight()) == 3
    for (s, d), link in fab.links.items():
        link.pop_completed(10.0)
    assert fab.in_flight() == []


def test_link_conservation_fuzz_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(st.integers(min_value=0, max_value=2 ** 63))
    def prop(seed):
        _run_link_program(random.Random(seed))

    prop()


# ---------------------------------------------------------------------------
# 9. TRANSFER-timer re-arm regression
# ---------------------------------------------------------------------------


def test_demand_completion_rearms_timer_for_unpaused_prefetch():
    """A prefetch paused behind demand traffic has eta=inf and produces
    no TRANSFER event of its own. When the demand transfer completes,
    the prefetch unpauses — the executor must re-arm the link timer *at
    that completion event*, or the prefetch stalls until an unrelated
    event happens to call advance. Regression: between t=8 and the
    t=108 completion nothing else is scheduled, so ``finish_upload``
    firing at exactly 12.0 proves the re-arm."""
    big = FunctionSpec("big", warm_time=100.0, cold_init=8.5,
                       mem_bytes=8 * GB)
    small = FunctionSpec("small", warm_time=1.0, cold_init=4.25,
                         mem_bytes=4 * GB)
    cfg = ServerConfig(policy="mqfq-sticky",
                       policy_kwargs={"T": 1000.0, "alpha": 0.3},
                       d=1, n_devices=1, capacity_bytes=64 * GB,
                       h2d_bw=1 * GB, datapath="pipeline", prefetch=True)
    srv = make_server(cfg, fns={"big": big, "small": small})
    cp = srv.control
    cp._sticky_dev["small"] = 0             # give the prefetch a target
    dev = cp.devices[0]
    uploads = []
    real = dev.mem.finish_upload
    dev.mem.finish_upload = \
        lambda fn, now: (uploads.append((fn, now)), real(fn, now))
    res = srv.run_trace([TraceEvent(0.0, "big"),
                         TraceEvent(0.5, "small")])
    # big's 8 GB demand transfer lands at 8.0; small's prefetch was
    # paused behind it and streams 4 GB right after: done at 12.0
    assert uploads == [("big", 8.0), ("small", 12.0)]
    assert not dev.datapath.transfers
    assert res.completed_count == 2


def test_peer_link_unpause_is_visible_through_next_eta():
    """Same stall shape on a fabric link: the executor arms from
    ``DeviceDataPath.next_eta()``, which must aggregate inbound peer
    links and surface the unpaused migration's fresh eta. (The
    wallclock executor has no modeled links at all — make_server
    rejects datapath='pipeline' there, asserted in
    test_datapath.py::test_pipeline_config_validation — so the sim
    executor is the only timer owner.)"""
    fabric, (m0, m1), (dp0, dp1) = _fabric_wired(p2p=8 * GB)
    _make_resident(m0, "d", 4 * GB)
    _make_resident(m0, "p", 2 * GB)
    m1.acquire("d", 4 * GB, 0.0)
    assert m1.begin_prefetch("p", 2 * GB, 0.0)
    assert dp1.transfers["p"].src == 0
    assert dp1.transfers["p"].eta == INF    # paused behind the demand
    assert dp1.next_eta() == 0.5            # d: 4 GB at 8 GB/s
    done = dp1.advance(0.5)
    assert [t.fn_id for t in done] == ["d"]
    # the unpause is immediately visible where the executor re-arms
    assert dp1.next_eta() == 0.75
    assert dp1.advance(0.75)[0].fn_id == "p"


# ---------------------------------------------------------------------------
# 10. config validation
# ---------------------------------------------------------------------------


def test_v2_config_validation():
    fns = function_copies(DEFAULT_MIX, 2)
    with pytest.raises(ValueError, match="placement"):
        make_server(ServerConfig(datapath="pipeline",
                                 placement="nearest"), fns=fns)
    with pytest.raises(ValueError, match="p2p_bw"):
        make_server(ServerConfig(p2p_bw=8 * GB), fns=fns)
    with pytest.raises(ValueError, match="chunk_bytes"):
        make_server(ServerConfig(chunk_bytes=GB), fns=fns)
    with pytest.raises(ValueError, match="time-to-resident"):
        make_server(ServerConfig(placement="time-to-resident"), fns=fns)
    with pytest.raises(ValueError, match="positive"):
        make_server(ServerConfig(datapath="pipeline", chunk_bytes=0),
                    fns=fns)
    with pytest.raises(ValueError, match="p2p_bw"):
        make_server(ServerConfig(datapath="pipeline", p2p_bw=-1.0),
                    fns=fns)
    # the defaults pass untouched
    make_server(ServerConfig(datapath="pipeline", p2p_bw=8 * GB,
                             chunk_bytes=GB,
                             placement="time-to-resident"), fns=fns)
