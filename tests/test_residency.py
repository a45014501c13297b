"""The memory managers alone decide which functions' weights sit on which
device, and the wall-clock executor's copies follow them: at every
completion a device holds a copy of a function only where its manager
has the region resident or an invocation of the function runs, and once
the server drains the copies are exactly the resident regions. Stub
endpoints, no JAX: each case takes seconds."""
import random
import sys
import threading
import time

import pytest

from repro.memory import GB
from repro.server import ServerConfig, StubEndpoint, make_server
from repro.workloads.spec import FunctionSpec

N_DEV = 4


def _spec(fn, gb=1):
    return FunctionSpec(fn, warm_time=0.002, cold_init=0.0,
                        mem_bytes=gb * GB, demand=0.2)


class GatedStub(StubEndpoint):
    """Holds every execution until the test opens ``gate``."""

    def __init__(self, fn, gate):
        super().__init__(fn, _spec(fn))
        self.gate = gate

    def execute(self, request=None, dev_id=0):
        assert self.gate.wait(10)
        return super().execute(request, dev_id)


def _server(eps, capacity_gb, mem_policy="prefetch"):
    cfg = ServerConfig(executor="wallclock", policy="mqfq-sticky", d=1,
                       n_devices=N_DEV, capacity_bytes=capacity_gb * GB,
                       mem_policy=mem_policy, pool_size=64)
    return make_server(cfg, endpoints=eps,
                       fns={f: ep.spec for f, ep in eps.items()})


def _stray(srv, eps, completing=None):
    """Copies that no resident region and no running invocation (nor the
    invocation completing right now) accounts for."""
    return [(dev.dev_id, fn) for dev in srv.control.devices
            for fn, ep in eps.items()
            if ep.resident_on(dev.dev_id)
            and not dev.mem.is_resident(fn, float("inf"))
            and not dev.running_fn_count.get(fn)
            and (dev.dev_id, fn) != completing]


def _copies_match_regions(srv, eps):
    """After a drain: a copy exactly where the region is resident. Read
    under the executor's lock, since the dispatcher's passes go on
    (an anticipation timeout may still evict a region)."""
    with srv.executor._lock:
        for dev in srv.control.devices:
            holders = {fn for fn, ep in eps.items()
                       if ep.resident_on(dev.dev_id)}
            regions = {fn for fn in eps
                       if dev.mem.is_resident(fn, float("inf"))}
            assert holders == regions, (dev.dev_id, holders, regions)


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.001)


def _rotating(mem_policy):
    """12 functions, room for 3 a device: the popular half moves on by
    two functions each phase, so regions are reserved, prefetched and
    evicted on every device while invocations run."""
    fns = [f"f{i}" for i in range(12)]
    eps = {f: StubEndpoint(f, _spec(f), delay=0.001) for f in fns}
    srv = _server(eps, 3, mem_policy)
    strays = []
    srv.bus.on_complete(lambda ev: strays.extend(
        _stray(srv, eps, (ev.device_id, ev.fn_id))))
    rng = random.Random(7)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # interleave the threads finely
    try:
        srv.start()
        for phase in range(6):
            hot = (fns[2 * phase:] + fns[:2 * phase])[:6]
            for _ in range(40):
                srv.submit(rng.choice(hot), {"seed": 0})
            srv.drain(timeout=60)
            _copies_match_regions(srv, eps)
        res = srv.stop()
    finally:
        sys.setswitchinterval(interval)
    assert res.failed_count == 0 and len(res.invocations) == 240
    assert strays == []
    assert sum(ep.evict_count for ep in eps.values()) > 0


def _no_room():
    """The manager finds no room for a function bigger than a device's
    budget, so it reserves nothing, and nothing is uploaded."""
    big = StubEndpoint("big", _spec("big", gb=2))
    big.compile(0)
    big.evict(0)                # compiled, no copy on any device
    srv = _server({"big": big}, 1)
    srv.start()
    srv.submit("big")           # turns its queue ACTIVE; never admitted
    time.sleep(0.2)             # a few dispatcher passes
    srv.stop()
    assert not any(d.mem.is_resident("big", float("inf"))
                   for d in srv.control.devices)
    assert big.upload_count == 0
    assert not any(big.resident_on(d) for d in range(N_DEV))


def _evicted_elsewhere():
    """``f`` runs on device 0 while devices 1-3 evict its regions for
    g1-g3: those copies go at once, device 0's when ``f`` completes."""
    gate = threading.Event()
    eps = {f: GatedStub(f, gate) for f in ("f", "g1", "g2", "g3")}
    srv = _server(eps, 1)
    devs = srv.control.devices
    srv.start()
    for _ in range(N_DEV):      # held at the gate: one on each device
        srv.submit("f")
    _wait(lambda: all(d.running_fn_count.get("f") for d in devs))
    gate.set()
    srv.drain(timeout=10)
    f = eps["f"]
    assert [f.resident_on(d) for d in range(N_DEV)] == [True] * N_DEV
    gate.clear()
    srv.submit("f")
    _wait(lambda: devs[0].running_fn_count.get("f"))
    for k, g in enumerate(("g1", "g2", "g3"), start=1):
        srv.submit(g)
        _wait(lambda: devs[k].running_fn_count.get(g))
    assert devs[0].running_fn_count.get("f") == 1
    assert [f.resident_on(d) for d in range(N_DEV)] == [True, False, False,
                                                        False]
    gate.set()
    srv.drain(timeout=10)
    _copies_match_regions(srv, eps)
    assert not f.resident_on(0)  # device 0 reserved g3 while f ran there
    res = srv.stop()
    assert res.failed_count == 0 and len(res.invocations) == N_DEV + 4


@pytest.mark.parametrize("case", [
    pytest.param(lambda: _rotating("prefetch"), id="rotating-prefetch"),
    pytest.param(lambda: _rotating("prefetch_swap"),
                 id="rotating-prefetch_swap"),
    pytest.param(_no_room, id="no-room"),
    pytest.param(_evicted_elsewhere, id="evicted-elsewhere"),
])
def test_weight_copies_follow_the_memory_managers(case):
    case()
