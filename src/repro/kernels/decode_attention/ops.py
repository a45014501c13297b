"""Jitted model-layout wrapper: decode q (B,1,H,dh) vs cache (B,S,KV,dh)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_bhd
from repro.models.attention import ring_slot_positions


@functools.partial(jax.jit,
                   static_argnames=("window", "ring", "interpret"))
def decode_attention(q, cache_k, cache_v, pos, *, window: int = 0,
                     ring: bool = False, interpret: Optional[bool] = None):
    B, one, H, dh = q.shape
    S, KV = cache_k.shape[1], cache_k.shape[2]
    G = H // KV
    if ring:
        slot_pos = ring_slot_positions(pos + 1, S)
    else:
        slot_pos = jnp.where(jnp.arange(S) <= pos, jnp.arange(S), -1)
    qg = q.reshape(B, KV, G, dh).reshape(B * KV, G, dh)
    kg = cache_k.transpose(0, 2, 1, 3).reshape(B * KV, S, dh)
    vg = cache_v.transpose(0, 2, 1, 3).reshape(B * KV, S, dh)
    out = decode_attention_bhd(qg, kg, vg, pos, slot_pos, window=window,
                               interpret=interpret)
    return out.reshape(B, KV, G, dh).reshape(B, 1, H, dh)


@functools.partial(jax.jit,
                   static_argnames=("window", "ring", "interpret"))
def decode_attention_quant(q, cache_k, k_scale, cache_v, v_scale, pos, *,
                           window: int = 0, ring: bool = False,
                           interpret: Optional[bool] = None):
    """Model-layout wrapper for the int8-cache kernel.

    q: (B,1,H,dh); cache_k/v: (B,S,KV,dh) int8; scales: (B,S,KV) f32."""
    from repro.kernels.decode_attention.kernel import decode_attention_bhd_q8
    B, one, H, dh = q.shape
    S, KV = cache_k.shape[1], cache_k.shape[2]
    G = H // KV
    if ring:
        slot_pos = ring_slot_positions(pos + 1, S)
    else:
        slot_pos = jnp.where(jnp.arange(S) <= pos, jnp.arange(S), -1)
    qg = q.reshape(B, KV, G, dh).reshape(B * KV, G, dh)
    kg = cache_k.transpose(0, 2, 1, 3).reshape(B * KV, S, dh)
    vg = cache_v.transpose(0, 2, 1, 3).reshape(B * KV, S, dh)
    ksg = k_scale.transpose(0, 2, 1).reshape(B * KV, S)
    vsg = v_scale.transpose(0, 2, 1).reshape(B * KV, S)
    out = decode_attention_bhd_q8(qg, kg, ksg, vg, vsg, pos, slot_pos,
                                  window=window, interpret=interpret)
    return out.reshape(B, KV, G, dh).reshape(B, 1, H, dh)
