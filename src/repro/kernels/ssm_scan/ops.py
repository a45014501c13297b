"""Jitted wrapper: model layout (B, S, Hs, P) + per-head A -> kernel rows."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan.kernel import ssm_scan_bhspn


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(x, dt, a_log, b, c, d_skip, *, chunk: int = 64,
             interpret: Optional[bool] = None):
    """x: (B,S,Hs,P); dt: (B,S,Hs); a_log/d_skip: (Hs,); b/c: (B,S,N).
    Returns y: (B,S,Hs,P) including the D*x skip."""
    B, S, Hs, P = x.shape
    N = b.shape[-1]
    A = -jnp.exp(a_log.astype(jnp.float32))                 # (Hs,)
    decay = jnp.exp(dt.astype(jnp.float32) * A)             # (B,S,Hs)
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * Hs, S, -1)
    xf = fold(x.astype(jnp.float32))
    decf = jnp.broadcast_to(decay.transpose(0, 2, 1)[..., None],
                            (B, Hs, S, N)).reshape(B * Hs, S, N)
    dtf = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(B * Hs, S, 1)
    bf = jnp.broadcast_to(b[:, None], (B, Hs, S, N)).reshape(B * Hs, S, N)
    cf = jnp.broadcast_to(c[:, None], (B, Hs, S, N)).reshape(B * Hs, S, N)
    y = ssm_scan_bhspn(xf, decf, dtf, bf.astype(jnp.float32),
                       cf.astype(jnp.float32), chunk=chunk,
                       interpret=interpret)
    y = y.reshape(B, Hs, S, P).transpose(0, 2, 1, 3)
    y = y + d_skip.astype(jnp.float32)[None, None, :, None] \
        * x.astype(jnp.float32)
    return y.astype(x.dtype)
