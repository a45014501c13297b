"""Plain float32 references of the served architectures, one module per
family, found by the ``reference`` key of a configuration file.

Each module gives, from the configuration file alone and importing
nothing of the program:

- ``param_table(cfg)``: the weight shapes and their initialisation;
- ``forward(cfg, params, tokens, positions, quant)``: logits at the
  given positions of a teacher-forced pass, in float32 at the highest
  matmul precision, or with every matmul operand rounded to ``quant``;
- ``work(cfg, batch, prompt, new_tokens)``: the operations and bytes that
  one prefill call and one decoded token need at least;
- ``program_fields(cfg)``: the served model's settings that must match.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp


def family(name: str):
    return importlib.import_module(f"reference.{name}")


# -- weights: the law the served endpoints draw their weights by ----------

def init_params(table, dtype, seed: int):
    """Weights from ``seed``: one key per leaf of the table in flattened
    (sorted-key) order, ``normal * fan_in ** -0.5`` drawn in float32 and
    stored in ``dtype``; norms start at one. One jitted program."""
    defs, treedef = jax.tree.flatten(table, is_leaf=lambda x: isinstance(x, tuple))

    def draw(rng):
        keys = jax.random.split(rng, len(defs))
        out = []
        for (shape, init), k in zip(defs, keys):
            if init == "ones":
                out.append(jnp.ones(shape, dtype))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * fan_in ** -0.5).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(draw)(jax.random.PRNGKey(seed))


def prompt_tokens(batch: int, prompt: int, vocab: int, request_seed: int):
    """The prompt a served request with ``{"seed": request_seed}`` runs:
    ``randint`` over the vocabulary from the second half of one split of
    the request's key."""
    _, sub = jax.random.split(jax.random.PRNGKey(request_seed))
    return jax.random.randint(sub, (batch, prompt), 0, vocab, dtype=jnp.int32)


# -- numerics shared by the families --------------------------------------

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def round_to(x, quant, axis=None):
    """``x`` (float32) rounded through ``quant``: "bfloat16", or "fp8"
    (e4m3 with a scale per row, or per tensor where ``axis`` is None,
    so that no element saturates). None leaves it."""
    if quant is None:
        return x
    if quant == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant != "fp8":
        raise ValueError(f"unknown precision {quant!r}")
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def mm(x, w, quant):
    """``x @ w`` in float32 at the highest precision, both operands first
    rounded through ``quant`` (activations per row, weights per tensor)."""
    return jnp.matmul(round_to(x, quant, axis=-1), round_to(w, quant),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w

