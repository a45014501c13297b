"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that
is only described, so these tests catch what interpret mode cannot: a
Pallas kernel Mosaic refuses, or a step that does not fit one chip's
HBM. Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library, and
all of this file's tests run in the worker that is given the file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import (decode_attention,
                                                decode_attention_quant)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mlstm_scan.ops import mlstm_scan
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.models import build_model, decode_cache_plan

HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    # x64 stays off as on the served path: a worker that has imported
    # repro.batchsim has it on, and Mosaic refuses 64-bit grid indices
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


BF = jnp.bfloat16
# model layout at real widths: qwen3-1.7b decode (GQA 16/8, dh 128) over
# a 4k cache and prefill at 2k; xlstm-350m's mLSTM (4 heads of 512);
# hymba-1.5b's SSM heads (25 x 64, state 16)
KERNELS = {
    "decode_attention": (
        decode_attention,
        [((2, 1, 16, 128), BF), ((2, 4096, 8, 128), BF),
         ((2, 4096, 8, 128), BF), ((), jnp.int32)]),
    "decode_attention_int8": (
        decode_attention_quant,
        [((2, 1, 16, 128), BF), ((2, 4096, 8, 128), jnp.int8),
         ((2, 4096, 8), jnp.float32), ((2, 4096, 8, 128), jnp.int8),
         ((2, 4096, 8), jnp.float32), ((), jnp.int32)]),
    "flash_attention": (
        flash_attention,
        [((1, 2048, 16, 128), BF), ((1, 2048, 8, 128), BF),
         ((1, 2048, 8, 128), BF)]),
    "mlstm_scan": (
        mlstm_scan,
        [((1, 256, 4, 512), jnp.float32)] * 3
        + [((1, 256, 4), jnp.float32)] * 2),
    "ssm_scan": (
        ssm_scan,
        [((1, 256, 25, 64), jnp.float32), ((1, 256, 25), jnp.float32),
         ((25,), jnp.float32), ((1, 256, 16), jnp.float32),
         ((1, 256, 16), jnp.float32), ((25,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [_sds(one_chip, s, d) for s, d in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_fits_one_v5e(one_chip):
    """The served decode step of qwen3-1.7b at its published widths
    (bf16, 28 layers, vocab 151936) compiles and fits one chip."""
    cfg = get_config("qwen3-1.7b")
    model = build_model(cfg)
    plan = decode_cache_plan(cfg, 64 + 4)
    on_chip = lambda t: jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    cache = on_chip(model.zero_cache(2, plan, abstract=True))

    def step(p, c, tok, pos):
        return model.decode_fn(p, c, tok, pos, ring=plan.ring)

    compiled = jax.jit(step).lower(
        params, cache, _sds(one_chip, (2, 1), jnp.int32),
        _sds(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 3.5e9 < mem.argument_size_in_bytes < total < HBM_BYTES


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "xlstm-350m"])
def test_served_decode_loop_fits_one_v5e(one_chip, arch):
    """The served decode loop at published widths (prompt 128, 16
    greedy tokens, batch 2) compiles for one chip, as one module with
    the steps in a device loop, and fits it."""
    from repro.runtime.device import served_programs
    from repro.shapes import InputShape
    cfg = get_config(arch)
    model = build_model(cfg)
    plan = decode_cache_plan(cfg, 128 + 16)
    programs = served_programs(model, plan, 16)
    on_chip = lambda t: jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    batch = on_chip(model.make_batch(InputShape("serve", 128, 2, "prefill"),
                                     abstract=True))
    logits, cache = on_chip(jax.eval_shape(programs["prefill"], params,
                                           batch))
    compiled = programs["decode_loop"].lower(
        params, cache, logits, _sds(one_chip, (), jnp.int32)).compile()
    assert compiled.as_text().startswith("HloModule jit__decode_loop")
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes < total < HBM_BYTES
