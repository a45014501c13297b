"""Single-token decode attention (flash-decoding) as a Pallas TPU kernel.

The decode hot-spot is HBM-bound: one query token streams the whole KV
cache. The kernel blocks the cache length into VMEM-sized tiles and keeps
the online-softmax state (m, l, acc) in VMEM scratch across tiles — one
pass over the cache, no (S)-sized intermediate in HBM. GQA: all G query
heads of one kv head ride in the same tile (rows of the q block), so the
cache tile is read once per kv head, not once per q head — the G-fold
arithmetic-intensity win GQA exists for.

Supports full caches (valid length = pos+1) and ring-buffer caches
(sliding window): masking is by slot *positions*, streamed as a (1, bs)
VMEM block per tile next to the cache tile (SMEM holds only the query
position: Mosaic loads scalars, not vectors, from SMEM).
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


def _valid(pos, k_pos, window: int):
    """(1, bs) mask of the slots the query at ``pos`` attends to; slot
    position -1 marks a slot never written."""
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window:
        valid &= k_pos > pos - window
    return valid


def _pad_slots(slot_pos, S: int, bs: int):
    """Slot positions as a (1, S_padded) int32 row, padded with -1."""
    pad = (-S) % bs
    slot_pos = slot_pos.astype(jnp.int32)
    if pad:
        slot_pos = jnp.pad(slot_pos, (0, pad), constant_values=-1)
    return slot_pos[None, :]


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   bs: int, window: int, scale: float):
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                    # (G, dh)
    k = k_ref[0]                    # (bs, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (G, bs)

    s = jnp.where(_valid(pos_ref[0], sp_ref[...], window), s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_bhd(q, k, v, pos, slot_pos, *, window: int = 0,
                         bs: int = 512, interpret: Optional[bool] = None):
    """q: (BH, G, dh) one token per kv-head row; k/v: (BH, S, dh);
    pos: scalar int32 query position; slot_pos: (S,) int32 absolute
    positions stored in each cache slot (-1 = never written)."""
    BH, G, dh = q.shape
    S = k.shape[1]
    bs = min(bs, S)
    pad = (-S) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    slots = _pad_slots(slot_pos, S, bs)
    ns = k.shape[1] // bs
    meta = jnp.asarray(pos, jnp.int32)[None]    # scalar prefetch: [pos]

    kernel = functools.partial(_decode_kernel, bs=bs, window=window,
                               scale=dh ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, ns),
        in_specs=[
            pl.BlockSpec((1, G, dh), lambda b, j, meta: (b, 0, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, j, meta: (b, j, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, j, meta: (b, j, 0)),
            pl.BlockSpec((1, bs), lambda b, j, meta: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, dh), lambda b, j, meta: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(meta, q, k, v, slots)
    return out


# --- int8-quantized KV variant (§Perf H5) --------------------------------------
#
# Same flash-decoding loop, but the cache tiles arrive in VMEM as int8
# plus one f32 scale per (slot, kv-head): HBM traffic for the dominant
# operand is halved, and dequantization happens on-chip right before the
# MXU dots. The per-slot scales are applied to the (G, bs) score and
# probability tiles rather than to the (bs, dh) cache tiles: a (1, bs)
# row broadcasts over sublanes only, where a per-slot column would need
# a lane-to-sublane relayout. The online-softmax state and masking are
# identical to the bf16 kernel.

def _decode_kernel_q8(pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, sp_ref,
                      o_ref, m_ref, l_ref, acc_ref, *,
                      bs: int, window: int, scale: float):
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                       # (G, dh)
    k = k_ref[0].astype(jnp.float32)                   # (bs, dh)
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (ks_ref[0] * scale)  # (G, bs)
    s = jnp.where(_valid(pos_ref[0], sp_ref[...], window), s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                   # (bs, dh)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p * vs_ref[0], v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_bhd_q8(q, k, k_scale, v, v_scale, pos, slot_pos, *,
                            window: int = 0, bs: int = 512,
                            interpret: Optional[bool] = None):
    """int8-cache decode. q: (BH, G, dh); k/v: (BH, S, dh) int8;
    k_scale/v_scale: (BH, S) f32 per-(slot, kv-head) scales."""
    BH, G, dh = q.shape
    S = k.shape[1]
    bs = min(bs, S)
    pad = (-S) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad)))
    slots = _pad_slots(slot_pos, S, bs)
    ns = k.shape[1] // bs
    # scales as (BH, 1, S): each tile's (1, bs) block spans its full
    # sublane dim, as Mosaic requires of a block's last two dims
    k_scale, v_scale = k_scale[:, None, :], v_scale[:, None, :]
    meta = jnp.asarray(pos, jnp.int32)[None]

    kernel = functools.partial(_decode_kernel_q8, bs=bs, window=window,
                               scale=dh ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, ns),
        in_specs=[
            pl.BlockSpec((1, G, dh), lambda b, j, meta: (b, 0, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, j, meta: (b, j, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, j, meta: (b, 0, j)),
            pl.BlockSpec((1, bs, dh), lambda b, j, meta: (b, j, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, j, meta: (b, 0, j)),
            pl.BlockSpec((1, bs), lambda b, j, meta: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, dh), lambda b, j, meta: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(meta, q, k, k_scale, v, v_scale, slots)
    return out
