"""Differential tests: the indexed device layer (heap-indexed memory
manager + warm pool, batched ``drain`` dispatch) must be bit-identical
to the seed's linear-scan implementations retained in
``repro.memory.reference``.

Three altitudes:

  1. Op-level fuzz: scripted pseudo-random op sequences (with deliberate
     timestamp collisions, so every LRU tie-break is exercised) driven
     through both implementations, comparing every return value, the
     sequence of residency announcements (every reservation and
     eviction), residency snapshots and byte counters —
     across all four memory policies.
  2. Control-plane replays under memory pressure: full traces through
     ``repro.server`` with ``device_layer="indexed"``+batched drain vs
     ``device_layer="reference"``+the seed's per-token dispatch loop,
     asserting identical dispatch/state-change/eviction sequences and
     metrics (exact float equality) for all four memory policies and for
     batched-vs-single dispatch in isolation.
  3. A serialized wall-clock run over stub endpoints: same comparisons on
     the time-free projections (wall timestamps differ run to run, the
     decision sequences must not).
"""
import itertools
import random

import pytest

from repro.memory import GB, make_device_layer
from repro.server import ServerConfig, StubEndpoint, make_server
from repro.workloads.spec import DEFAULT_MIX, FunctionSpec, function_copies
from repro.workloads.traces import TraceEvent, azure_trace, zipf_trace

MEM_POLICIES = ("ondemand", "madvise", "prefetch", "prefetch_swap")


# ---------------------------------------------------------------------------
# 1. op-level fuzz
# ---------------------------------------------------------------------------

def drive_manager(cls, mem_policy: str, seed: int):
    """Scripted op sequence; returns every observable the manager has."""
    rng = random.Random(seed)
    m = cls(capacity_bytes=8 * GB, h2d_bw=4 * GB, policy=mem_policy)
    announced = []          # every reservation and eviction, in order
    m.residency_listeners.append(
        lambda fn, res: announced.append((fn, res)))
    fns = [f"f{i}" for i in range(24)]
    sizes = {f: (1 + i % 5) * (GB // 2) for i, f in enumerate(fns)}
    log = []
    t = 0.0
    for _ in range(800):
        # coarse clock: repeated timestamps force last_use ties, so the
        # creation-order tie-break is actually exercised
        t = round(t + rng.choice([0.0, 0.0, 0.25, 0.5]), 3)
        f = rng.choice(fns)
        op = rng.randrange(5)
        if op == 0:
            m.on_queue_active(f, sizes[f], t)
        elif op == 1:
            m.on_queue_idle(f, t)
        elif op == 2:
            log.append(("acquire", f, m.acquire(f, sizes[f], t)))
        elif op == 3:
            log.append(("admit", f,
                        m.admit(f, sizes[f], rng.randrange(8) * GB, t)))
        else:
            running = {g: sizes[g] for g in rng.sample(fns, 3)}
            log.append(("admit_dict", f,
                        m.admit(f, sizes[f], running, t)))
        log.append((m.used, m.free_bytes(),
                    tuple(f2 for f2 in fns if m.is_resident(f2, t))))
    log.append(("totals", m.bytes_uploaded, m.bytes_evicted,
                m.prefetch_count))
    return announced, log


@pytest.mark.parametrize("mem_policy", MEM_POLICIES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_manager_op_equivalence(mem_policy, seed):
    fast = drive_manager(make_device_layer("indexed")[0], mem_policy, seed)
    ref = drive_manager(make_device_layer("reference")[0], mem_policy, seed)
    assert fast[0] == ref[0], "residency announcements diverged"
    for i, (a, b) in enumerate(itertools.zip_longest(fast[1], ref[1])):
        assert a == b, f"op #{i} diverged: indexed={a} reference={b}"


def drive_second_pass(cls):
    """Force the reference's quirk path: the evictable pool cannot satisfy
    the request, so its *pre-eviction* resident snapshot is re-walked and
    the phase-1 victims are double-counted. The indexed layer must replay
    that bug-for-bug."""
    m = cls(capacity_bytes=6 * GB, h2d_bw=100 * GB, policy="prefetch")
    evicts = []
    m.residency_listeners.append(
        lambda fn, res: res or evicts.append(fn))
    m.acquire("a", 1 * GB, 0.0)      # will become the lone evictable
    m.acquire("b", 2 * GB, 1.0)      # stays non-evictable (never idled)
    m.acquire("c", 2 * GB, 2.0)
    m.on_queue_idle("a", 3.0)        # prefetch: marks evictable, no swap
    # free = 1 GB; need 6: phase 1 evicts a (free 2), still short ->
    # second pass re-walks [a, b, c] (a's accounting repeats)
    ready, mult = m.acquire("d", 6 * GB, 4.0)
    return (evicts, ready, mult, m.bytes_evicted, m.bytes_uploaded,
            m.used, sorted(f for f in "abcd" if m.is_resident(f, 100.0)))


def test_manager_second_pass_quirk_equivalence():
    fast = drive_second_pass(make_device_layer("indexed")[0])
    ref = drive_second_pass(make_device_layer("reference")[0])
    assert fast == ref
    evicts = fast[0]
    assert evicts.count("a") == 2, \
        "the pre-snapshot second pass must re-count phase-1 victims"


def drive_pool(cls, seed: int):
    rng = random.Random(seed)
    p = cls(max_containers=12)
    fns = [f"f{i}" for i in range(8)]
    busy = []
    log = []
    t = 0.0
    for _ in range(700):
        t = round(t + rng.choice([0.0, 0.0, 0.5]), 2)  # force ties
        roll = rng.random()
        if roll < 0.5 or not busy:
            f = rng.choice(fns)
            c, st = p.acquire(f, t, rng.random() < 0.5)
            busy.append(c)
            log.append(("acq", f, st))
        elif roll < 0.92:
            c = busy.pop(rng.randrange(len(busy)))
            p.release(c, t)
            log.append(("rel", c.fn_id))
        else:
            f = rng.choice(fns)
            p.evict_fn(f)
            log.append(("evict_fn", f))
        log.append((tuple(p.count(f) for f in fns), p.count(),
                    p.evictions))
        # the live-container view must agree in content AND order
        log.append(tuple(c.fn_id for c in p.containers))
    log.append(("stats", p.cold_starts, p.warm_starts,
                p.host_warm_starts, p.evictions, p.cold_hit_pct))
    return log


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_pool_op_equivalence(seed):
    fast = drive_pool(make_device_layer("indexed")[1], seed)
    ref = drive_pool(make_device_layer("reference")[1], seed)
    for i, (a, b) in enumerate(itertools.zip_longest(fast, ref)):
        assert a == b, f"pool op #{i} diverged: indexed={a} reference={b}"


# ---------------------------------------------------------------------------
# 2. control-plane replays under memory pressure
# ---------------------------------------------------------------------------

N_FNS = 16
FNS = function_copies(DEFAULT_MIX, N_FNS)
TRACES = {
    "zipf": zipf_trace(FNS, duration=150.0, total_rps=4.0, seed=1),
    "azure": azure_trace(FNS, duration=200.0, trace_id=3),
}
# ~2 regions fit per device: constant misses, evictions and admission
# refusals — the regime where the device layer actually decides things.
# strict_reclaim=True: these suites assert bit-identity against the
# reference layer, which IS the seed (always strict); the indexed layer
# defaults to the clean single-count reclaim since PR 6, so the
# comparison must opt back into the seed's double-count semantics
PRESSURE = dict(d=2, n_devices=2, capacity_bytes=3 * GB, pool_size=8,
                strict_reclaim=True)


def replay(trace_name, *, policy="mqfq-sticky", policy_kwargs=None,
           **server_kw):
    cfg = ServerConfig(policy=policy,
                       policy_kwargs=policy_kwargs or {"T": 5.0},
                       **server_kw)
    srv = make_server(cfg, fns=FNS)
    dispatches, states, evicts = [], [], []
    srv.bus.on_dispatch(lambda ev: dispatches.append(
        (ev.inv.inv_id, ev.fn_id, ev.device_id, ev.start_type, ev.time)))
    srv.bus.on_state_change(lambda ev: states.append(
        (ev.fn_id, ev.old.value, ev.new.value, ev.time)))
    for dev in srv.control.devices:
        dev.mem.residency_listeners.append(
            lambda fn, res, i=dev.dev_id: res or evicts.append((i, fn)))
    res = srv.run_trace(TRACES[trace_name])
    summary = {
        "n": len(res.invocations),
        "mean": res.mean_latency(),
        "p99": res.p99_latency(),
        "starts": res.start_type_counts(),
        "pool": (res.pool.cold_starts, res.pool.warm_starts,
                 res.pool.host_warm_starts, res.pool.evictions),
        "bytes": [(d.mem.bytes_uploaded, d.mem.bytes_evicted,
                   d.mem.prefetch_count) for d in srv.control.devices],
        "gaps": [w.max_gap for w in res.fairness.windows],
        "util": res.mean_utilization(),
    }
    return dispatches, states, evicts, summary


def assert_replays_equal(fast, ref):
    names = ("dispatch", "state change", "eviction")
    for k in range(3):
        for i, (a, b) in enumerate(itertools.zip_longest(fast[k], ref[k])):
            assert a == b, f"{names[k]} #{i} diverged: {a} vs {b}"
    assert fast[3] == ref[3]


@pytest.mark.parametrize("trace_name", ["zipf", "azure"])
@pytest.mark.parametrize("mem_policy", MEM_POLICIES)
def test_device_layer_equivalence_under_pressure(trace_name, mem_policy):
    """Indexed layer + batched drain vs reference layer + the seed's
    per-token loop: the full observable behavior must match exactly."""
    fast = replay(trace_name, mem_policy=mem_policy,
                  device_layer="indexed", batch_dispatch=True, **PRESSURE)
    ref = replay(trace_name, mem_policy=mem_policy,
                 device_layer="reference", batch_dispatch=False, **PRESSURE)
    assert_replays_equal(fast, ref)


@pytest.mark.parametrize("mem_policy", ["prefetch_swap", "ondemand"])
def test_batched_vs_single_dispatch(mem_policy):
    """Isolate the drain() batching: same device layer, batched vs the
    legacy one-try_dispatch-per-call loop."""
    fast = replay("azure", mem_policy=mem_policy,
                  device_layer="indexed", batch_dispatch=True, **PRESSURE)
    ref = replay("azure", mem_policy=mem_policy,
                 device_layer="indexed", batch_dispatch=False, **PRESSURE)
    assert_replays_equal(fast, ref)


def test_reference_layer_with_reference_scheduler():
    """Full-stack cross-check: indexed scheduler core + indexed device
    layer + drain vs reference scheduler core + reference device layer +
    single-step dispatch — the complete seed pipeline."""
    fast = replay("azure", policy="mqfq-sticky",
                  device_layer="indexed", batch_dispatch=True, **PRESSURE)
    ref = replay("azure", policy="ref-mqfq-sticky",
                 device_layer="reference", batch_dispatch=False, **PRESSURE)
    assert_replays_equal(fast, ref)


def test_random_policy_pressure_equivalence():
    """Plain MQFQ consumes RNG per choose(): batching must not change
    how many candidate lists are drawn."""
    fast = replay("zipf", policy="mqfq", policy_kwargs={"T": 5.0, "seed": 7},
                  device_layer="indexed", batch_dispatch=True, **PRESSURE)
    ref = replay("zipf", policy="mqfq", policy_kwargs={"T": 5.0, "seed": 7},
                 device_layer="reference", batch_dispatch=False, **PRESSURE)
    assert_replays_equal(fast, ref)


# ---------------------------------------------------------------------------
# 3. wall-clock executor, serialized for determinism
# ---------------------------------------------------------------------------

def _wallclock_run(device_layer: str):
    """One-at-a-time submits through the wall-clock executor: every
    invocation completes before the next arrives, so the decision
    sequence is deterministic even though wall timestamps are not.
    Tight capacity (2 of 3 regions fit) forces evictions + host_warm."""
    fns = {f: FunctionSpec(f, warm_time=0.01, cold_init=0.0,
                           mem_bytes=int(0.45 * GB), demand=0.4)
           for f in ("f0", "f1", "f2")}
    endpoints = {f: StubEndpoint(f, s) for f, s in fns.items()}
    cfg = ServerConfig(executor="wallclock", policy="mqfq-sticky",
                       policy_kwargs={"T": 10.0, "alpha": 1e6},
                       d=1, n_devices=1, capacity_bytes=1 * GB,
                       pool_size=2, device_layer=device_layer)
    srv = make_server(cfg, endpoints=endpoints, fns=fns)
    log, evicts = [], []
    srv.bus.on_dispatch(lambda ev: log.append(
        (ev.fn_id, ev.device_id, ev.start_type)))
    dev = srv.control.devices[0]
    dev.mem.residency_listeners.append(
        lambda fn, res: res or evicts.append(fn))
    srv.start()
    for f in ["f0", "f1", "f2"] * 4:
        srv.submit(f, {"seed": 0})
        srv.drain(timeout=30.0)
    res = srv.stop()
    return (log, evicts,
            (res.pool.cold_starts, res.pool.warm_starts,
             res.pool.host_warm_starts, res.pool.evictions),
            (dev.mem.bytes_uploaded, dev.mem.bytes_evicted))


def test_wallclock_device_layer_equivalence():
    fast = _wallclock_run("indexed")
    ref = _wallclock_run("reference")
    assert fast == ref
    # sanity: the scenario actually exercised the pressure paths
    assert fast[2][3] > 0, "expected warm-pool evictions"
    assert fast[1], "expected memory swap-outs"
