"""``JaxEndpoint.execute`` reports the bytes of each kind of cache its
decode loop carried, attention KV and recurrent state, equal to the cache
arrays' ``nbytes``; the wall-clock executor copies both onto the
``inv.execute`` span."""
import time

import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.runtime.device import JaxEndpoint
from repro.server import ServerConfig, StubEndpoint, make_server
from repro.workloads.spec import FunctionSpec


def _nbytes(tree):
    return sum(x.nbytes for x in jax.tree.leaves(tree))


# which part of each architecture's cache is KV, and which is state
KINDS = {
    "qwen3-1.7b": (lambda c: c, lambda c: {}),
    "xlstm-350m": (lambda c: {}, lambda c: c),
    "granite-4.0-h-micro": (lambda c: c["attention"], lambda c: c["mamba"]),
}


@pytest.mark.parametrize("arch", sorted(KINDS))
def test_counters_equal_the_cache_arrays(arch):
    ep = JaxEndpoint(arch, get_config(arch).reduced(), serve_seq=16,
                     decode_steps=2)
    ep.compile(0)
    out = ep.execute({"seed": 5}, 0)
    batch = ep.model.make_batch(ep.serve_shape, rng=jax.random.PRNGKey(5))
    _, cache = ep.prefill(batch)
    kv, state = KINDS[arch]
    assert out["kv_bytes"] == _nbytes(kv(cache))
    assert out["state_bytes"] == _nbytes(state(cache))
    assert out["kv_bytes"] + out["state_bytes"] == _nbytes(cache) > 0


class CachedStub(StubEndpoint):
    """A stub that reports the cache counters a ``JaxEndpoint`` does."""

    def execute(self, request=None, dev_id=0):
        return dict(super().execute(request, dev_id), kv_bytes=8_650_752,
                    state_bytes=152_875_008)


def test_the_execute_span_carries_the_counters():
    obs.RECORDER.clear()
    spec = FunctionSpec("cc", warm_time=0.01, cold_init=0.0, mem_bytes=1024,
                        demand=0.2)
    ep = CachedStub("cc", spec, delay=0.002)
    srv = make_server(ServerConfig(executor="wallclock", policy="mqfq-sticky",
                                   d=1, n_devices=1), endpoints={"cc": ep},
                      fns={"cc": spec})
    srv.start()
    for _ in range(3):
        srv.submit("cc")
        time.sleep(0.005)
    srv.drain(timeout=30.0)
    srv.stop()
    spans = [s for s in obs.RECORDER.spans("inv.execute") if s.fn == "cc"]
    obs.RECORDER.clear()
    assert len(spans) == 3
    for s in spans:
        assert s.attrs["kv_bytes"] == 8_650_752
        assert s.attrs["state_bytes"] == 152_875_008
