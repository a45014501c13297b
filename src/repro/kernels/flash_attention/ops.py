"""Jitted model-layout wrapper for flash attention.

Model layout: q (B, S, H, dh), k/v (B, S, KV, dh) (GQA). The wrapper
folds the GQA group into the query rows per kv head — each (batch, kv
head) pair becomes one kernel program row — and restores the layout.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: Optional[bool] = None):
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    # (B, S, KV, G, dh) -> (B*KV, G*S, dh): group rows share the kv head
    qg = q.reshape(B, Sq, KV, G, dh).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(B * KV, G * Sq, dh)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, -1, dh)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, -1, dh)
    if G == 1:
        out = flash_attention_bhsd(qg, kg, vg, causal=causal,
                                   window=window, interpret=interpret)
    else:
        # each group member attends independently: vmap over the group
        qs = qg.reshape(B * KV, G, Sq, dh)
        out = jax.vmap(
            lambda qq: flash_attention_bhsd(
                qq, kg, vg, causal=causal, window=window,
                interpret=interpret),
            in_axes=1, out_axes=1)(qs)
        out = out.reshape(B * KV, G * Sq, dh)
    out = out.reshape(B, KV, G, Sq, dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sq, H, dh)
