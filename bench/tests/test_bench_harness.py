"""Whole runs on the CPU at a small size: the harness finds a cell, its
configuration, traffic and metric from files alone; a sound run comes out
correct; the timed path broken underneath makes it come out not correct;
the control reads wider gaps than the program; and the command refuses to
run without an accelerator."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import control
from harness import cell as cell_mod
from harness import spec
from tiny_configs import DATA, tiny_configs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = [name for name, _ in tiny_configs()]


def new_cell_root(tmp_path, config="tiny-qwen3"):
    """A checkout holding only a new cell's files: a configuration, a
    traffic mix and a metric reader, listed in its BENCHMARK.json."""
    b = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (b / sub).mkdir(parents=True)
    shutil.copy(os.path.join(DATA, f"{config}.json"), b / "configs")
    shutil.copy(os.path.join(DATA, "tiny.json"), b / "traffic" / "trickle.json")
    (b / "metrics" / "warm_share.py").write_text(
        "def read(ctx):\n"
        "    return 100.0 * sum(r.start_type == 'warm' for r in ctx.records)"
        " / len(ctx.records)\n")
    (b / "metrics" / "latency_p50_ms.py").write_text(
        open(os.path.join(BENCH, "metrics", "latency_p50_ms.py")).read())
    bench = {
        "configs": [{"name": config, "file": f"bench/configs/{config}.json"}],
        "workloads": [{"name": "tiny.trickle", "config": config,
                       "traffic": "trickle", "chips": 1}],
        "end_to_end": [{"name": "latency_p50_ms", "unit": "ms"},
                       {"name": "warm_share", "unit": "%",
                        "workloads": ["other.cell"]}],
        "per_layer": [{"name": "warm_share", "unit": "%",
                       "workloads": ["tiny.trickle"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def tiny_run(cell, seed=2**31 + 77, traced=False):
    return cell_mod.run(cell, seed, 2.0, traced, t_start=time.monotonic(),
                        devs=jax.devices(), bytes_limit=10**9,
                        peak={"bf16_flops": 1e12, "hbm_bytes_s": 1e11})


def test_a_new_cell_is_found_from_files_and_runs_correct(tmp_path):
    cell = spec.load_cell("tiny.trickle", root=new_cell_root(tmp_path))
    assert [m.name for m in cell.end_to_end] == ["latency_p50_ms"]
    assert [m.name for m in cell.per_layer] == ["warm_share"]
    assert cell.config["name"] == "tiny-qwen3"
    assert cell.traffic["rate"]["inv_s"] == 6.0
    out = tiny_run(cell, traced=True)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    assert list(out["metrics"]) == ["warm_share"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["token_gap"]["value"] <= 1e-3


def test_the_benchmark_resolves_every_cell_and_metric():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]
        assert cell.config["gap_limit"] > 0


def _tokens_altered(monkeypatch):
    from repro.runtime.device import JaxEndpoint
    orig = JaxEndpoint.execute

    def execute(self, request=None, dev_id=0):
        out = orig(self, request, dev_id)
        out["tokens"][0, -1] = (out["tokens"][0, -1] + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(JaxEndpoint, "execute", execute)


def _state_unchanged(monkeypatch):
    from repro.models.model import Model
    orig = Model.decode_fn

    def decode_fn(self, params, cache, tokens, pos, ring=False):
        logits, _ = orig(self, params, cache, tokens, pos, ring)
        return logits, cache
    monkeypatch.setattr(Model, "decode_fn", decode_fn)


def _half_batch(monkeypatch):
    from repro.runtime.device import JaxEndpoint
    orig = JaxEndpoint.execute

    def execute(self, request=None, dev_id=0):
        out = orig(self, request, dev_id)
        out["tokens"][1] = out["tokens"][0]
        return out
    monkeypatch.setattr(JaxEndpoint, "execute", execute)


def _wrong_weights(monkeypatch):
    from repro.runtime.device import JaxEndpoint
    orig = JaxEndpoint.upload
    uploaded = []

    def upload(self, dev_id=0):
        t = orig(self, dev_id)
        others = [ep for ep in uploaded if ep is not self]
        if others:   # the weights of the function uploaded before it
            self.device_params[dev_id] = jax.device_put(others[-1].host_params)
        uploaded.append(self)
        return t
    monkeypatch.setattr(JaxEndpoint, "upload", upload)


@pytest.mark.parametrize("fault", [_tokens_altered, _state_unchanged,
                                   _half_batch, _wrong_weights])
@pytest.mark.parametrize("config", CONFIGS)
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault,
                                            config):
    fault(monkeypatch)
    cell = spec.load_cell("tiny.trickle", root=new_cell_root(tmp_path, config))
    out = tiny_run(cell)
    assert out["correct"] is False
    assert out["checks"]["token_gap"]["value"] > 1e-3


@pytest.mark.parametrize("config", CONFIGS)
def test_control_reads_wider_gaps_than_the_program(config):
    cell = spec.Cell(name="t", chips=1, config_name=config,
                     config=spec.load_json(os.path.join(DATA, f"{config}.json")),
                     traffic_name="tiny",
                     traffic=spec.load_json(os.path.join(DATA, "tiny.json")))
    seeds = [11, 12, 13, 14]
    out = control.readings(cell, seeds, 2.0, t_start=time.monotonic(),
                           devs=jax.devices(), bytes_limit=10**9)
    limit = cell.config["gap_limit"]
    assert max(v["served"] for v in out.values()) <= limit
    assert max(v["control"] for v in out.values()) > limit


def test_the_command_refuses_a_host_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "qwen3-warm", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("functions", [None, 2])
def test_the_check_samples_every_function_or_a_drawn_few(functions):
    import numpy as np
    from harness import check
    served = [check.Served(i % 5, i % 5, i, np.zeros((2, 4), int),
                           "cold" if i < 5 else "warm") for i in range(40)]
    picked = check.sample(served, 8, 77, functions)
    assert len(picked) == 8 and len({id(p) for p in picked}) == 8
    fns = {p.fn for p in picked}
    assert len(fns) == (5 if functions is None else 2)
    assert check.sample(served, 8, 77, functions) == picked
    if functions:       # another seed draws other functions
        assert any({p.fn for p in check.sample(served, 8, s, functions)}
                   != fns for s in range(78, 84))
