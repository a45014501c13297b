"""The served path's own spans (``repro.obs``): what the wall-clock
executor and ``JaxEndpoint`` record, how the spans of one invocation fit
together, and the names the device trace's readers match."""
import importlib.util
import os
import re
import sys
import time

import pytest

from repro import obs
from repro.core.flow import QueueState
from repro.server import ServerConfig, StateChangeEvent, StubEndpoint, \
    make_server
from repro.workloads.spec import FunctionSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAIN = ("inv.queue", "inv.handoff", "inv.lock_wait", "inv.execute",
         "inv.complete")


@pytest.fixture(autouse=True)
def empty_recorder():
    obs.RECORDER.clear()
    yield
    obs.RECORDER.clear()


def _spec(fn, warm=0.01):
    return FunctionSpec(fn, warm_time=warm, cold_init=0.0, mem_bytes=1024,
                        demand=0.2)


def _serve(eps, n_per_fn, d=2, gap=0.0):
    fns = {f: ep.spec for f, ep in eps.items()}
    srv = make_server(ServerConfig(executor="wallclock", policy="mqfq-sticky",
                                   policy_kwargs={"T": 5.0}, d=d,
                                   n_devices=1), endpoints=eps, fns=fns)
    srv.start()
    for _ in range(n_per_fn):
        for f in eps:
            srv.submit(f)
        time.sleep(gap)
    srv.drain(timeout=30.0)
    res = srv.stop()
    return srv, res


def _by_invocation(fns):
    out = {}
    for s in obs.RECORDER.spans():
        if s.fn in fns and s.inv is not None:
            out.setdefault(s.inv, {})[s.name] = s
    return out


def test_an_invocations_spans_are_contiguous_and_sum_to_its_latency():
    eps = {f: StubEndpoint(f, _spec(f), delay=0.003)
           for f in ("obs-a", "obs-b", "obs-c")}
    srv, res = _serve(eps, n_per_fn=4, gap=0.002)
    assert res.completed_count == 12 and res.failed_count == 0
    t0 = srv.executor._t0
    spans = _by_invocation(eps)
    for inv in res.invocations:
        got = spans[inv.inv_id]
        assert set(CHAIN) <= set(got)
        chain = [got[n] for n in CHAIN[:3]]
        if "inv.compile" in got:            # each function's first start
            chain.append(got["inv.compile"])
        chain += [got["inv.execute"], got["inv.complete"]]
        assert {s.fn for s in chain} == {inv.fn_id}
        for a, b in zip(chain, chain[1:]):
            assert b.start == a.end, (a.name, b.name)
        assert chain[0].start == pytest.approx(inv.arrival + t0, abs=1e-9)
        assert chain[-1].end == pytest.approx(inv.completion + t0, abs=1e-9)
        total = sum(s.end - s.start for s in chain)
        assert total == pytest.approx(inv.completion - inv.arrival, abs=1e-6)
        # overhead is the lock wait and the compile, nothing else
        pre = sum(s.end - s.start for s in chain[2:-2])
        assert inv.overhead == pytest.approx(pre, abs=1e-9)
        ex = got["inv.execute"]
        assert ex.attrs == {"device_wait_s": 0.0}
        assert ex.end - ex.start >= 0.003


def test_a_second_invocation_at_d2_waits_on_the_lock_for_the_first():
    ep = StubEndpoint("obs-slow", _spec("obs-slow", warm=0.2), delay=0.2)
    ep.compile(0)
    _, res = _serve({"obs-slow": ep}, n_per_fn=2, d=2)
    assert res.completed_count == 2
    spans = _by_invocation({"obs-slow"})
    # both were dispatched before either finished: d=2 gave the function
    # two tokens, and the endpoint runs one invocation at a time
    first, second = sorted(spans.values(),
                           key=lambda g: g["inv.lock_wait"].end)
    assert second["inv.queue"].end < first["inv.execute"].end
    # the second began to wait while the first executed, and held the
    # lock only once the first had released it, after its execute span
    wait, ex = second["inv.lock_wait"], first["inv.execute"]
    assert wait.start < ex.end <= wait.end <= second["inv.execute"].start
    by_id = {inv.inv_id: inv for inv in res.invocations}
    for g in (first, second):
        w = g["inv.lock_wait"]
        assert by_id[w.inv].overhead == pytest.approx(w.end - w.start,
                                                      abs=1e-9)


def test_a_lock_wait_span_that_fails_still_releases_the_lock(monkeypatch):
    """A failure in recording the lock wait fails that invocation, but
    the endpoint's lock is released: the next invocation still runs."""
    ep = StubEndpoint("obs-leak", _spec("obs-leak"), delay=0.01)
    ep.compile(0)
    add, failed = obs.RECORDER.add, []

    def add_or_fail(s):
        if s.name == "inv.lock_wait" and not failed:
            failed.append(s.inv)
            raise RuntimeError("recorder failed")
        add(s)

    monkeypatch.setattr(obs.RECORDER, "add", add_or_fail)
    srv = make_server(ServerConfig(executor="wallclock", policy="mqfq-sticky",
                                   d=1, n_devices=1), endpoints={"obs-leak": ep},
                      fns={"obs-leak": ep.spec})
    srv.start()
    srv.submit("obs-leak")
    srv.submit("obs-leak")
    with pytest.raises(RuntimeError, match="recorder failed"):
        srv.drain(timeout=10.0)
    leaked = ep.lock.locked()
    if leaked:
        ep.lock.release()           # let the waiting invocation finish
    res = srv.stop()
    assert not leaked
    assert [i.failed for i in sorted(res.invocations,
                                     key=lambda i: i.inv_id)] == [True, False]


def test_a_throttled_queue_leaves_a_span():
    eps = {"obs-t": StubEndpoint("obs-t", _spec("obs-t"))}
    srv = make_server(ServerConfig(executor="wallclock", policy="mqfq-sticky",
                                   d=1, n_devices=1), endpoints=eps,
                      fns={"obs-t": eps["obs-t"].spec})
    bus, t0 = srv.control.bus, srv.executor._t0
    bus.emit_state_change(StateChangeEvent(
        "obs-t", QueueState.ACTIVE, QueueState.THROTTLED, 1.25))
    assert obs.RECORDER.spans("mqfq.throttled") == []      # still open
    bus.emit_state_change(StateChangeEvent(
        "obs-t", QueueState.THROTTLED, QueueState.INACTIVE, 2.5))
    (s,) = obs.RECORDER.spans("mqfq.throttled")
    assert (s.fn, s.inv) == ("obs-t", None)
    assert (s.start, s.end) == (1.25 + t0, 2.5 + t0)
    srv.stop()


def test_the_simulator_records_nothing():
    from repro.workloads.traces import zipf_trace
    fns = {f"obs-s{i}": _spec(f"obs-s{i}") for i in range(3)}
    srv = make_server(ServerConfig(policy="mqfq-sticky", d=2), fns=fns)
    res = srv.run_trace(zipf_trace(fns, duration=5.0, total_rps=20.0, seed=1))
    assert res.completed_count > 0
    assert obs.RECORDER.spans() == []


def test_the_recorder_is_bounded():
    assert obs.MAXLEN == 65536
    assert obs.RECORDER._spans.maxlen == obs.MAXLEN
    rec = obs.Recorder(maxlen=8)
    for i in range(20):
        rec.add(obs.Span("inv.queue", i, "f", float(i), i + 0.5, {}))
    kept = rec.spans()
    assert [s.inv for s in kept] == list(range(12, 20))
    assert [s.inv for s in rec.spans("inv.queue", lo=14.0, hi=16.0)] == [
        14, 15, 16]
    assert rec.spans("inv.execute") == []
    rec.clear()
    assert rec.spans() == []


def test_a_span_records_its_block_and_an_earlier_start():
    with obs.span("inv.upload", 7, "f", bytes=3) as sp:
        pass
    with obs.span("inv.execute", 7, "f", start=sp.end) as sp2:
        sp2.attrs["device_wait_s"] = 0.5
    a, b = obs.RECORDER.spans()
    assert (a.name, a.inv, a.fn, a.attrs) == ("inv.upload", 7, "f",
                                              {"bytes": 3})
    assert a.start <= a.end == b.start <= b.end
    assert b.attrs == {"device_wait_s": 0.5}


def _program_span_names():
    """Every span name the program passes to ``obs`` or to a profiler
    annotation, read from its source."""
    pat = re.compile(
        r'(?:obs\.span|obs\.record|TraceAnnotation)\(\s*"([^"]+)"')
    names = set()
    for d, _, files in os.walk(os.path.join(ROOT, "src", "repro")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    names.update(pat.findall(fh.read()))
    return names


def test_no_program_span_takes_a_benchmark_span_name(monkeypatch):
    path = os.path.join(ROOT, "bench", "harness", "tracing.py")
    spec = importlib.util.spec_from_file_location("bench_tracing", path)
    tracing = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, tracing)
    spec.loader.exec_module(tracing)
    names = _program_span_names()
    assert set(CHAIN) | {"inv.compile", "inv.upload",
                         "mqfq.throttled"} == names
    assert all("." in n for n in names)
    assert not names & set(tracing.SPANS)


@pytest.fixture(scope="module")
def reduced_endpoint():
    from repro.runtime.device import build_endpoints
    ep = build_endpoints({"obs-q": ("qwen3-1.7b", 0)}, serve_seq=8,
                         serve_batch=2, decode_steps=3)["obs-q"]
    ep.compile(0)
    return ep


def test_the_served_programs_keep_the_module_names_the_trace_reads(
        reduced_endpoint):
    """The device trace's readers find prefill and decode by the XLA
    module names ``jit__prefill`` and ``jit__decode``; decode work is
    counted by modules whose name holds ``decode``, as the served decode
    loop's does."""
    import jax.numpy as jnp
    ep = reduced_endpoint
    params = ep.device_params[0]
    batch = ep.model.make_batch(ep.serve_shape)
    prefill = ep._compiled["prefill"].lower(params, batch)
    assert "module @jit__prefill " in prefill.as_text()
    logits, cache = ep.prefill(batch)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    decode = ep._compiled["decode"].lower(params, cache, tok,
                                          ep.prompt_len(batch))
    assert "module @jit__decode " in decode.as_text()
    loop = ep._compiled["decode_loop"].lower(params, cache, logits,
                                             ep.prompt_len(batch))
    (name,) = re.findall(r"^module @(\S+) ", loop.as_text(), re.M)
    assert "decode" in name and "prefill" not in name


def test_execute_dispatches_prefill_then_the_decode_loop_alone(
        reduced_endpoint, monkeypatch):
    """One execute runs two programs, prefill and the decode loop; no
    argmax is dispatched on its own between or after them."""
    import jax.numpy as jnp
    ep = reduced_endpoint
    calls, eager = [], []

    def spy(key, program):
        def call(*args):
            calls.append(key)
            return program(*args)
        return call

    for key, program in dict(ep._compiled).items():
        monkeypatch.setitem(ep._compiled, key, spy(key, program))
    argmax = jnp.argmax

    def eager_argmax(*args, **kw):
        eager.append(args)
        return argmax(*args, **kw)

    monkeypatch.setattr(jnp, "argmax", eager_argmax)
    ep.execute({"seed": 1})
    assert calls == ["prefill", "decode_loop"]
    assert eager == []


def test_execute_reports_its_waits_on_the_device(reduced_endpoint):
    ep = reduced_endpoint
    srv = make_server(ServerConfig(executor="wallclock", policy="mqfq-sticky",
                                   d=1, n_devices=1), endpoints={"obs-q": ep})
    srv.start()
    srv.submit("obs-q", {"seed": 3})
    srv.drain(timeout=120)
    (inv,) = srv.stop().invocations
    (ex,) = obs.RECORDER.spans("inv.execute")
    out = inv.output
    assert ex.attrs["device_wait_s"] == out["device_wait_s"]
    assert ex.attrs["host_syncs"] == out["host_syncs"] == 1
    assert 0.0 < out["device_wait_s"] < out["exec_s"] <= ex.end - ex.start
