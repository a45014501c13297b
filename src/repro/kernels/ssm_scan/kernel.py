"""Selective state-space scan (Mamba-style, Hymba SSM heads) in Pallas.

Same TPU adaptation as the mLSTM kernel: the per-head state S (P x N)
lives in VMEM scratch across the sequential chunk grid dimension — HBM
sees only inputs and outputs, never the state. The per-step decay
exp(dt*A) is precomputed by the ops wrapper (elementwise, XLA does it
well); the kernel owns the recurrence, which XLA cannot fuse into a
state-resident loop on its own. The per-step decay is a scalar per
head, but it arrives spread over the N state columns, as B and C do:
a (1, N) row scales the (P, N) state by broadcasting over sublanes
only, while Mosaic cannot broadcast a (1, 1) value over sublanes and
lanes at once.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _ssm_kernel(x_ref, decay_ref, dt_ref, b_ref, c_ref, y_ref, s_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    def step(t, _):
        x_t = x_ref[0, pl.ds(t, 1)]          # (1, P)
        dec = decay_ref[0, pl.ds(t, 1)]      # (1, N), one value
        dt = dt_ref[0, pl.ds(t, 1)]          # (1, 1)
        b_t = b_ref[0, pl.ds(t, 1)]          # (1, N)
        c_t = c_ref[0, pl.ds(t, 1)]          # (1, N)
        # S <- S * decay + (dt x)^T B : (P, N)
        upd = jax.lax.dot_general(
            dt * x_t, b_t, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s_ref[...] = s_ref[...] * dec + upd
        # y = S C^T : (P, 1) -> (1, P)
        y = jax.lax.dot_general(
            s_ref[...], c_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        y_ref[0, pl.ds(t, 1)] = y.T.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


def ssm_scan_bhspn(x, decay, dt, b, c, *, chunk: int = 64,
                   interpret: Optional[bool] = None):
    """x: (BH, S, P); dt: (BH, S, 1); decay/b/c: (BH, S, N).
    Returns y: (BH, S, P) (without the D*x skip, added by the caller)."""
    BH, S, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        z3 = ((0, 0), (0, pad), (0, 0))
        x = jnp.pad(x, z3)
        dt = jnp.pad(dt, z3)
        b = jnp.pad(b, z3)
        c = jnp.pad(c, z3)
        decay = jnp.pad(decay, z3, constant_values=1.0)
    nc = x.shape[1] // chunk

    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    spec = lambda w: pl.BlockSpec((1, chunk, w), lambda bi, ci: (bi, ci, 0))
    out = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[spec(P), spec(N), spec(1), spec(N), spec(N)],
        out_specs=spec(P),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, decay, dt, b, c)
    return out[:, :S]
