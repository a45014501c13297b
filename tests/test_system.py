"""End-to-end behaviour through the unified ``repro.server`` control
plane: simulation integration + real JAX wall-clock engine + the legacy
deprecation shims."""
import pytest

from repro.memory.manager import GB
from repro.server import ServerConfig, make_server
from repro.workloads.costmodel import endpoint_mix, endpoint_spec
from repro.workloads.traces import make_workload, zipf_trace


def sim(fns, trace, **kw):
    policy_kwargs = kw.pop("policy_kwargs", {})
    policy = kw.pop("policy", "mqfq-sticky")
    cfg = ServerConfig(policy=policy, policy_kwargs=policy_kwargs, **kw)
    return make_server(cfg, fns=fns).run_trace(trace)


@pytest.fixture(scope="module")
def medium_workload():
    return make_workload("azure", n_fns=19, duration=200.0, trace_id=4)


def test_sim_completes_all(medium_workload):
    fns, trace = medium_workload
    res = sim(fns, trace, d=2)
    assert all(i.done for i in res.invocations)
    assert res.mean_latency() > 0


def test_mqfq_beats_fcfs_on_medium_trace(medium_workload):
    """Headline claim (Fig. 5c/6a): MQFQ-Sticky cuts latency vs FCFS."""
    fns, trace = medium_workload
    fcfs = sim(fns, trace, policy="fcfs", d=2)
    mqfq = sim(fns, trace, d=2)
    assert mqfq.mean_latency() < fcfs.mean_latency()
    assert mqfq.pool.cold_hit_pct <= fcfs.pool.cold_hit_pct + 1.0


def test_memory_policies_ordering(medium_workload):
    """Fig. 4: prefetch_swap <= ondemand; madvise >= ondemand."""
    fns, trace = medium_workload
    lat = {}
    for pol in ["prefetch_swap", "ondemand", "madvise"]:
        res = sim(fns, trace, d=2, mem_policy=pol, h2d_bw=12 * GB,
                  capacity_bytes=8 * GB)
        lat[pol] = res.mean_latency()
    assert lat["prefetch_swap"] <= lat["ondemand"] * 1.05
    assert lat["madvise"] >= lat["ondemand"] * 0.95


def test_multi_device_scales(medium_workload):
    fns, trace = medium_workload
    one = sim(fns, trace, n_devices=1, d=2)
    two = sim(fns, trace, n_devices=2, d=2)
    assert two.mean_latency() < one.mean_latency()


def test_dynamic_d_respects_threshold(medium_workload):
    fns, trace = medium_workload
    res = sim(fns, trace, d=3, dynamic_d=True)
    for dev in res.devices:
        assert 1 <= dev.tokens.current_d <= 3


def test_run_sim_shim_matches_new_api(medium_workload):
    """The deprecation shim must drive the same control plane."""
    from repro.core.policies import make_policy
    from repro.runtime.simulate import run_sim

    fns, trace = medium_workload
    old = run_sim(make_policy("mqfq-sticky"), fns, trace, d=2)
    new = sim(fns, trace, d=2)
    assert old.mean_latency() == new.mean_latency()
    assert old.p99_latency() == new.p99_latency()
    assert ([i.start_type for i in old.invocations]
            == [i.start_type for i in new.invocations])


def test_endpoint_specs_reasonable():
    for shape in ["decode_32k", "prefill_32k"]:
        mix = endpoint_mix(shape)
        assert len(mix) == 11
        for s in mix.values():
            assert 0 < s.warm_time < 300
            assert s.cold_init > 1.0
            assert s.mem_bytes > 100e6


def test_endpoint_serving_sim():
    """The paper's scheduler serving the assigned architectures."""
    fns = endpoint_mix("decode_32k")
    trace = zipf_trace(fns, duration=120.0, total_rps=2.0, seed=0)
    res = sim(fns, trace, d=2, capacity_bytes=256 * GB, h2d_bw=100 * GB)
    assert all(i.done for i in res.invocations)


def test_long500k_mix_excludes_whisper():
    mix = endpoint_mix("long_500k")
    assert not any("whisper" in k for k in mix)
    assert len(mix) == 10


@pytest.mark.slow
def test_real_engine_end_to_end():
    """Wall-clock executor over real JAX endpoints, via the legacy
    ServingEngine shim (so the shim path stays covered)."""
    import random
    import time as _time

    from repro.configs import get_config
    from repro.core.policies import make_policy
    from repro.runtime.device import JaxEndpoint
    from repro.runtime.engine import ServingEngine

    archs = ["qwen3-1.7b", "xlstm-350m"]
    eps = {a: JaxEndpoint(a, get_config(a).reduced(), seed=i,
                          serve_seq=32, decode_steps=2)
           for i, a in enumerate(archs)}
    eng = ServingEngine(eps, make_policy("mqfq-sticky", T=5.0), d=2)
    eng.start()
    rng = random.Random(0)
    for i in range(8):
        eng.submit(rng.choice(archs), {"seed": i})
        _time.sleep(0.01)
    eng.drain(timeout=300)
    res = eng.stop()
    assert len(eng.completed) == 8
    assert all(i.done for i in eng.completed)
    types = {i.start_type for i in eng.completed}
    assert "cold" in types and "warm" in types
    # the unified control plane now gives the wall-clock path warm-pool
    # and utilization accounting the old engine lacked
    assert res is not None and sum(res.start_type_counts().values()) == 8
    assert res.pool.cold_starts >= len(archs)
