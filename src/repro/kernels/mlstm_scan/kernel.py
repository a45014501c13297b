"""mLSTM recurrence (xLSTM matrix memory) as a Pallas TPU kernel.

TPU adaptation of the chunkwise-recurrent mLSTM: the per-head matrix
memory C (dh x dh), normalizer n and stabilizer m stay in VMEM scratch
for the *entire* sequence (grid dim over chunks is sequential), so HBM
traffic is only the q/k/v/gate inputs and the h outputs — the state never
round-trips. On GPU this is done with warp-resident registers; the VMEM-
scratch-across-grid-steps pattern is the TPU-native equivalent
(DESIGN.md hardware-adaptation notes).

Time steps within a chunk run as an in-kernel fori_loop: the recurrence
is inherently sequential; the kernel's win is memory locality, not
parallelism across time.

The per-step gates are scalars per head. Mosaic cannot broadcast a
(1, 1) value over both sublanes and lanes of the (dh, dh) memory, so
each gate is first spread along the lanes into a (1, dh) row (and the
stabilizer m is kept as such a row): the row then broadcasts over
sublanes only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref, o_ref,
                  c_ref, n_ref, m_ref, *, chunk: int, dh: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    def step(t, _):
        q_t = q_ref[0, pl.ds(t, 1)]          # (1, dh)
        k_t = k_ref[0, pl.ds(t, 1)]
        v_t = v_ref[0, pl.ds(t, 1)]
        row = lambda g: jnp.broadcast_to(g, (1, dh))
        ig = row(ig_ref[0, pl.ds(t, 1)])     # (1, dh), one value
        fg = row(fg_ref[0, pl.ds(t, 1)])
        logf = jax.nn.log_sigmoid(fg)
        m_prev = m_ref[...]                  # (1, dh), one value
        m_new = jnp.maximum(logf + m_prev, ig)
        i_p = jnp.exp(ig - m_new)            # (1, dh)
        f_p = jnp.exp(logf + m_prev - m_new)
        # C <- f C + v^T (i k): (dh, dh); the gates scale columns, and
        # every column gets the same factor
        c_ref[...] = f_p * c_ref[...] + jax.lax.dot_general(
            v_t, i_p * k_t, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        n_ref[...] = f_p * n_ref[...] + i_p * k_t
        m_ref[...] = m_new
        # h = (C q) / max(|n . q|, 1)
        num = jax.lax.dot_general(
            q_t, c_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (1, dh)
        den = jnp.maximum(
            jnp.abs(jnp.sum(n_ref[...] * q_t, axis=-1, keepdims=True)), 1.0)
        o_ref[0, pl.ds(t, 1)] = (num / den).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


def mlstm_scan_bhsd(q, k, v, ig, fg, *, chunk: int = 64,
                    interpret: Optional[bool] = None):
    """q/k/v: (BH, S, dh) f32; ig/fg: (BH, S, 1) gate pre-activations.
    Returns h: (BH, S, dh)."""
    BH, S, dh = q.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        z = ((0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(a, z) for a in (q, k, v))
        ig = jnp.pad(ig, z, constant_values=NEG_INF)  # no-op inputs
        fg = jnp.pad(fg, z, constant_values=30.0)     # f -> 1
    nc = q.shape[1] // chunk

    kernel = functools.partial(_mlstm_kernel, chunk=chunk, dh=dh)
    seq_spec = pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0))
    gate_spec = pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0))
    out = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[seq_spec, seq_spec, seq_spec, gate_spec, gate_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v, ig, fg)
    return out[:, :S]
