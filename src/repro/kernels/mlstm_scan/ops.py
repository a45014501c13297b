"""Jitted model-layout wrapper: (B, S, H, dh) heads -> kernel rows."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.mlstm_scan.kernel import mlstm_scan_bhsd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_scan(q, k, v, ig, fg, *, chunk: int = 64, interpret: Optional[bool] = None):
    """q/k/v: (B, S, H, dh); ig/fg: (B, S, H). Returns (B, S, H, dh)."""
    B, S, H, dh = q.shape
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, a.shape[-1])
    qf, kf, vf = fold(q), fold(k), fold(v)
    igf = ig.transpose(0, 2, 1).reshape(B * H, S, 1)
    fgf = fg.transpose(0, 2, 1).reshape(B * H, S, 1)
    out = mlstm_scan_bhsd(qf.astype(jnp.float32), kf.astype(jnp.float32),
                          vf.astype(jnp.float32), igf.astype(jnp.float32),
                          fgf.astype(jnp.float32), chunk=chunk,
                          interpret=interpret)
    return out.reshape(B, H, S, dh).transpose(0, 2, 1, 3).astype(q.dtype)
