"""The references against the program at a small size on the CPU, for
every tiny configuration: the same weights from the same seed, bit for
bit, and the same logits."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
from tiny_configs import DATA, tiny_configs

CASES = tiny_configs()


def setup(name, arch, dtype):
    from repro.configs import get_config
    from repro.models import build_model
    with open(os.path.join(DATA, f"{name}.json")) as f:
        conf = dict(json.load(f), torch_dtype=dtype)
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype=dtype,
                              param_dtype=dtype)
    return conf, cfg, build_model(cfg), reference.family(conf["reference"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name,arch", CASES)
def test_weights_equal_the_programs_bit_for_bit(name, arch, dtype):
    conf, cfg, model, fam = setup(name, arch, dtype)
    want = jax.jit(model.init_params)(jax.random.PRNGKey(1234))
    got = reference.init_params(fam.param_table(conf), jnp.dtype(dtype), 1234)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("name,arch", CASES)
def test_logits_equal_the_programs_forward(name, arch):
    from repro.models import transformer, xlstm_stack
    conf, cfg, model, fam = setup(name, arch, "float32")
    params = reference.init_params(fam.param_table(conf), jnp.float32, 7)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0,
                                conf["vocab_size"], dtype=jnp.int32)
    fwd = xlstm_stack.forward if cfg.family == "ssm" else transformer.forward
    with jax.default_matmul_precision("highest"):
        want = fwd(cfg, params, tokens)[0]
    got = fam.forward(conf, params, tokens, np.arange(24))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", [n for n, _ in CASES])
def test_every_tiny_configuration_resolves(name):
    from repro.configs import get_config
    with open(os.path.join(DATA, f"{name}.json")) as f:
        conf = json.load(f)
    reference.family(conf["reference"])
    get_config(conf["program"]["arch"])


def test_prompt_is_the_served_requests_prompt():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.shapes import InputShape
    model = build_model(get_config("qwen3-1.7b").reduced())
    batch = model.make_batch(InputShape("serve", 16, 2, "prefill"),
                             rng=jax.random.PRNGKey(987654321))
    np.testing.assert_array_equal(
        reference.prompt_tokens(2, 16, 512, 987654321), batch["tokens"])


def test_fp8_rounding_keeps_scale_and_loses_bits():
    x = jnp.linspace(-900.0, 900.0, 4097, dtype=jnp.float32)
    y = reference.round_to(x, "fp8")
    assert bool(jnp.all(jnp.isfinite(y)))
    rel = jnp.abs(y - x) / jnp.maximum(jnp.abs(x), 1.0)
    assert 1e-3 < float(rel.max()) <= 2 ** -4
    assert reference.round_to(x, None) is x
