"""The served path on the CPU: endpoint failures fail the run, weights
land on the device the control plane picked, the compile cache lands
where it should, and ``chip_smoke.py`` refuses to run without a TPU."""
import os
import subprocess
import sys
import textwrap
import time

import pytest

from repro.server import ServerConfig, StubEndpoint, make_server
from repro.workloads.spec import FunctionSpec

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")


class BrokenEndpoint(StubEndpoint):
    """Raises a plain error (not an injected FaultError) from one op."""

    def __init__(self, op: str):
        super().__init__("f", FunctionSpec("f", 0.01, 0.0, 1))
        self.op = op

    def _maybe_raise(self, op):
        if op == self.op:
            raise RuntimeError(f"{op} exploded")

    def compile(self, dev_id=0):
        self._maybe_raise("compile")
        return super().compile(dev_id)

    def upload(self, dev_id=0):
        self._maybe_raise("upload")
        return super().upload(dev_id)

    def execute(self, request=None, dev_id=0):
        self._maybe_raise("execute")
        return super().execute(request, dev_id)


def _server(ep):
    cfg = ServerConfig(executor="wallclock", policy="mqfq-sticky",
                       n_devices=1, d=1)
    return make_server(cfg, endpoints={"f": ep})


@pytest.mark.parametrize("op", ["compile", "upload", "execute"])
def test_endpoint_error_fails_invocation_and_drain(op):
    ep = BrokenEndpoint(op)
    if op == "upload":
        # compiled but evicted: both the anticipatory prefetch and the
        # dispatch have to upload
        StubEndpoint.compile(ep)
        ep.evict(0)
    srv = _server(ep)
    srv.start()
    srv.submit("f", {"seed": 0})
    with pytest.raises(RuntimeError, match=f"{op} exploded"):
        srv.drain(timeout=10)
    res = srv.stop()                    # already raised: tears down quietly
    (inv,) = res.invocations
    assert inv.done and inv.failed and res.failed_count == 1


def test_stop_reraises_an_endpoint_error_when_drain_was_skipped():
    srv = _server(BrokenEndpoint("execute"))
    srv.start()
    inv = srv.submit("f", {"seed": 0})
    deadline = time.monotonic() + 10
    while not inv.done and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(RuntimeError, match="execute exploded"):
        srv.stop()
    assert inv.failed


def test_clean_run_still_drains_and_stops():
    ep = BrokenEndpoint("none")
    srv = _server(ep)
    srv.start()
    for i in range(3):
        srv.submit("f", {"seed": i})
    srv.drain(timeout=10)
    res = srv.stop()
    assert res.failed_count == 0 and len(res.invocations) == 3
    assert all(i.output == {"exec_s": 0.01} for i in res.invocations)


def test_stub_residency_is_per_device():
    ep = StubEndpoint("f", FunctionSpec("f", 0.0, 0.0, 1))
    ep.compile(2)
    ep.upload(3)
    ep.evict(2)
    assert not ep.resident_on(2) and ep.resident_on(3)
    ep.execute(dev_id=3)
    with pytest.raises(AssertionError):
        ep.execute(dev_id=2)


PLACEMENT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.runtime.device import build_endpoints
    from repro.server import ServerConfig, make_server

    devs = jax.devices()
    assert len(devs) == 4
    fns = {f"{a}/s{s}": (a, s) for a in ("qwen3-1.7b", "xlstm-350m")
           for s in (0, 1)}
    eps = build_endpoints(fns, serve_seq=16, decode_steps=2)

    # one copy per device; eviction drops only that device's copy
    ep = eps["qwen3-1.7b/s0"]
    ep.upload(2)
    ep.upload(3)
    for d in (2, 3):
        assert {x for l in jax.tree.leaves(ep.device_params[d])
                for x in l.devices()} == {devs[d]}
    ep.evict(2)
    assert not ep.resident_on(2) and ep.resident_on(3)
    ep.evict(3)

    def serve(n_devices):
        srv = make_server(ServerConfig(executor="wallclock", n_devices=n_devices,
                                       d=1), endpoints=eps)
        srv.start()
        for i, fn in enumerate(list(fns) * 2):
            srv.submit(fn, {"seed": i})
        srv.drain(timeout=300)
        return srv, srv.stop()

    _, ref = serve(1)
    want = {(i.fn_id, i.request["seed"]): i.output["tokens"]
            for i in ref.invocations}
    for e in eps.values():
        e.evict(0)
    srv, res = serve(4)
    assert res.failed_count == 0 and len(res.invocations) == 8
    assert sorted({i.device_id for i in res.invocations}) == [0, 1, 2, 3]
    for inv in res.invocations:
        out = inv.output
        assert out["device"] == devs[inv.device_id], inv
        assert out["weight_devices"] == {devs[inv.device_id]}, inv
        assert (out["tokens"] == want[(inv.fn_id, inv.request["seed"])]).all()

    # the memory manager's evict listeners are bound per device
    fn = res.invocations[0].fn_id
    ep = eps[fn]
    for d in (0, 1):
        if not ep.resident_on(d):
            ep.upload(d)
    for cb in srv.control.devices[1].mem.evict_listeners:
        cb(fn)
    assert ep.resident_on(0) and not ep.resident_on(1)
    print("PLACEMENT_OK")
""")


def _run(code, env_extra=None, cwd=None, args=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    cmd = [sys.executable] + (["-c", code] if code else []) + list(args)
    return subprocess.run(cmd, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=600)


def test_weights_follow_placement_across_four_devices(tmp_path):
    r = _run(PLACEMENT_SCRIPT,
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PLACEMENT_OK" in r.stdout


CACHE_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.runtime.device import CACHE_DIR, configure_compile_cache
    path = configure_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    print("PATH", path, "DEFAULT", CACHE_DIR)
    if path != str(CACHE_DIR):
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
""")


def test_compile_cache_lands_in_the_env_dir(tmp_path):
    d = tmp_path / "jaxcache"
    r = _run(CACHE_SCRIPT, {"JAX_COMPILATION_CACHE_DIR": str(d)})
    assert r.returncode == 0, r.stderr[-3000:]
    assert f"PATH {d} " in r.stdout
    assert d.is_dir() and any(d.iterdir())


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-c", CACHE_SCRIPT],
                       env={**env, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    fields = r.stdout.split()
    path = fields[fields.index("PATH") + 1]
    assert path == fields[fields.index("DEFAULT") + 1]
    assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _run(None, args=[os.path.join(ROOT, "chip_smoke.py")])
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout
    assert "endpoint init" not in r.stdout      # failed before any model


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "xlstm-350m",
                                  "whisper-large-v3", "hymba-1.5b"])
def test_execute_decodes_the_tokens_of_the_stepwise_path(arch):
    """``execute`` runs the greedy decode loop as one device program and
    reads its tokens once; they are the tokens of prefill followed by
    ``decode_steps`` single decode steps, each fed its host argmax."""
    import jax
    import numpy as np

    from repro.runtime.device import build_endpoints
    ep = build_endpoints({"f": (arch, 0)}, serve_seq=8, serve_batch=2,
                         decode_steps=3)["f"]
    ep.compile(0)
    for seed in (0, 5):
        out = ep.execute({"seed": seed})
        batch = ep.model.make_batch(ep.serve_shape,
                                    rng=jax.random.PRNGKey(seed))
        logits, cache = ep.prefill(batch)
        pos, want = ep.prompt_len(batch), []
        for i in range(ep.decode_steps):
            tok = np.argmax(np.asarray(logits), -1)[:, None].astype(np.int32)
            logits, cache = ep.decode(cache, tok, pos + i)
            want.append(np.argmax(np.asarray(logits), -1)[:, None])
        toks = out["tokens"]
        assert toks.shape == (2, ep.decode_steps) and toks.dtype == np.int32
        assert (toks == np.concatenate(want, axis=1)).all()
        assert out["host_syncs"] == 1
