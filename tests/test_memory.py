"""Memory manager + warm pool unit tests (paper §4.3, Fig. 4/8c).

Parametrized over both device-layer implementations — the indexed hot
paths and the seed's linear scans retained in ``repro.memory.reference``
— so the behavioral contract is pinned on each directly (the full
differential is in ``tests/test_memory_equivalence.py``).
"""
import pytest

from repro.memory import make_device_layer
from repro.memory.manager import GB, MADVISE_DISPATCH_OVERHEAD


@pytest.fixture(params=["indexed", "reference"])
def layer(request):
    return make_device_layer(request.param)


@pytest.fixture
def manager_cls(layer):
    return layer[0]


@pytest.fixture
def pool_cls(layer):
    return layer[1]


class TestManager:
    def test_prefetch_on_activation_is_async(self, manager_cls):
        m = manager_cls(16 * GB, h2d_bw=1 * GB, policy="prefetch_swap")
        m.on_queue_active("f", 2 * GB, now=0.0)
        assert m.is_resident("f", 3.0)   # upload eta = 2.0
        ready, mult = m.acquire("f", 2 * GB, now=0.5)
        assert ready == pytest.approx(2.0)  # wait only the remainder
        assert mult == 1.0
        ready, _ = m.acquire("f", 2 * GB, now=5.0)
        assert ready == pytest.approx(5.0)  # fully warm: no wait

    def test_swap_on_idle_frees_capacity(self, manager_cls):
        m = manager_cls(4 * GB, policy="prefetch_swap")
        m.on_queue_active("a", 3 * GB, 0.0)
        m.on_queue_idle("a", 1.0)
        assert not m.is_resident("a", 1.0)
        m.on_queue_active("b", 3 * GB, 2.0)
        assert m.is_resident("b", 100.0)

    def test_lru_eviction_order(self, manager_cls):
        m = manager_cls(6 * GB, policy="prefetch_swap")
        for i, t in enumerate([0.0, 1.0, 2.0]):
            m.acquire(f"f{i}", 2 * GB, t)
        for i in range(3):
            m.on_queue_idle(f"f{i}", 3.0)
        # all were swapped out on idle under prefetch_swap; re-acquire two
        m.acquire("f0", 2 * GB, 4.0)
        m.acquire("f1", 2 * GB, 5.0)
        m.acquire("f2", 4 * GB, 6.0)  # needs eviction: f0 is LRU
        assert not m.is_resident("f0", 10.0)
        assert m.is_resident("f2", 10.0)

    def test_lru_tie_breaks_by_creation_order(self, manager_cls):
        """Equal last_use: Python's stable sort broke ties by region
        creation order; the heap key pins the same rule."""
        m = manager_cls(6 * GB, policy="prefetch")
        for name in ("a", "b", "c"):
            m.acquire(name, 2 * GB, 1.0)     # identical last_use
            m.on_queue_idle(name, 2.0)       # evictable, still resident
        evicts = []
        m.residency_listeners.append(
            lambda fn, res: res or evicts.append(fn))
        m.acquire("d", 4 * GB, 3.0)
        assert evicts == ["a", "b"]

    def test_ondemand_stretches_execution(self, manager_cls):
        m = manager_cls(16 * GB, h2d_bw=1 * GB, policy="ondemand")
        ready, mult = m.acquire("f", 2 * GB, 0.0)
        assert ready == 0.0          # no upfront wait...
        assert mult > 1.0            # ...but execution pays the paging

    def test_madvise_overhead_no_benefit(self, manager_cls):
        m = manager_cls(16 * GB, policy="madvise")
        m.acquire("f", GB, 0.0)
        ready, _ = m.acquire("f", GB, 1.0)
        assert ready == pytest.approx(1.0 + MADVISE_DISPATCH_OVERHEAD)

    def test_admission_control(self, manager_cls):
        m = manager_cls(4 * GB)
        assert m.admit("f", 2 * GB, {}, 0.0)
        assert not m.admit("f", 2 * GB, {"g": 3 * GB}, 0.0)

    def test_admission_control_presummed(self, manager_cls):
        """The control plane now passes its O(1) running-bytes counter."""
        m = manager_cls(4 * GB)
        assert m.admit("f", 2 * GB, 0, 0.0)
        assert m.admit("f", 2 * GB, 2 * GB, 0.0)
        assert not m.admit("f", 2 * GB, 3 * GB, 0.0)


class TestWarmPool:
    def test_start_type_progression(self, pool_cls):
        p = pool_cls(4)
        c, t = p.acquire("f", 0.0, device_resident=False)
        assert t == "cold"
        p.release(c, 1.0)
        c, t = p.acquire("f", 2.0, device_resident=True)
        assert t == "warm"
        p.release(c, 3.0)
        c, t = p.acquire("f", 4.0, device_resident=False)
        assert t == "host_warm"  # paper: "GPU-cold but host-warm"

    def test_concurrent_same_fn_needs_new_container(self, pool_cls):
        p = pool_cls(4)
        c1, t1 = p.acquire("f", 0.0, True)
        c2, t2 = p.acquire("f", 0.0, True)
        assert t1 == "cold" and t2 == "cold"  # ref [65] spawn-start effect
        assert c1 is not c2

    def test_lru_eviction_at_capacity(self, pool_cls):
        p = pool_cls(2)
        for i, t in enumerate([0.0, 1.0]):
            c, _ = p.acquire(f"f{i}", t, True)
            p.release(c, t + 0.5)
        c, _ = p.acquire("f2", 2.0, True)   # evicts f0 (LRU)
        assert p.count("f0") == 0
        assert p.count("f1") == 1
        _, t = p.acquire("f0", 3.0, True)
        assert t == "cold"

    def test_count_is_maintained_incrementally(self, pool_cls):
        """Satellite: count(fn) was an O(pool) scan; both layers must
        agree on the counter semantics through the full lifecycle."""
        p = pool_cls(8)
        cs = [p.acquire("f", float(i), True)[0] for i in range(3)]
        g, _ = p.acquire("g", 3.0, True)
        assert p.count("f") == 3 and p.count("g") == 1 and p.count() == 4
        for c in cs[:2]:
            p.release(c, 4.0)
        assert p.count("f") == 3            # released, still pooled
        p.evict_fn("f")                      # drops idle f only
        assert p.count("f") == 1            # the busy one survives
        assert p.count() == 2
        p.release(cs[2], 5.0)
        p.release(g, 5.0)
        assert p.count("f") == 1 and p.count() == 2
        assert p.count("nope") == 0

    def test_cold_hit_pct(self, pool_cls):
        p = pool_cls(8)
        c, _ = p.acquire("f", 0.0, True)
        p.release(c, 1.0)
        for i in range(9):
            c, _ = p.acquire("f", float(i + 2), True)
            p.release(c, float(i + 2) + 0.5)
        assert p.cold_hit_pct == pytest.approx(10.0)
