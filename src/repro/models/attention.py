"""Attention: GQA full/causal/sliding-window, chunked prefill, cached decode.

Pure-jnp implementations (the XLA path used for dry-run lowering and CPU
smoke tests). The Pallas TPU kernels in ``repro.kernels`` implement the
same math for the hot paths and are validated against these references.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.utils.shardctx import batch_axis, maybe_shard

NEG_INF = -1e30


def _scores(q, k, scale):
    # q: (B, Sq, KV, G, dh)  k: (B, Sk, KV, dh) -> (B, KV, G, Sq, Sk)
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32) * scale


def _combine(w, v):
    # w: (B, KV, G, Sq, Sk)  v: (B, Sk, KV, dh) -> (B, Sq, KV, G, dh)
    return jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v)


def masked_attention(q, k, v, q_pos, k_pos, *, causal: bool = True,
                     window: int = 0, scale: Optional[float] = None):
    """Attention with positional masking.

    q: (B, Sq, H, dh) grouped into (KV, G); k/v: (B, Sk, KV, dh).
    q_pos: (Sq,) absolute positions of queries; k_pos: (Sk,) of keys
    (entries < 0 are invalid slots, e.g. unfilled ring-buffer slots).
    """
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, dh)
    scale = scale if scale is not None else dh ** -0.5
    s = _scores(qg, k, scale)  # (B, KV, G, Sq, Sk) f32
    # distributed softmax: shard the KEY dim of the score matrix over the
    # model axis (head counts are often not divisible by the axis, the key
    # length is) — GSPMD turns the softmax reductions and the value
    # contraction into small all-reduces instead of replicating the f32
    # score block on every chip
    s = maybe_shard(s, batch_axis(), None, None, None, "model")
    mask = k_pos[None, :] >= 0
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = _combine(w, v)
    return out.reshape(B, Sq, H, dh)


def chunked_attention(q, k, v, q_pos, k_pos, *, causal: bool = True,
                      window: int = 0, chunk: int = 1024,
                      scale: Optional[float] = None):
    """Query-chunked attention: peak memory O(chunk * Sk) instead of
    O(Sq * Sk). Used for long prefill (32k) where the full score matrix
    would not fit per-chip HBM."""
    B, Sq, H, dh = q.shape
    if Sq % chunk or Sq <= chunk:
        return masked_attention(q, k, v, q_pos, k_pos,
                                causal=causal, window=window, scale=scale)
    n = Sq // chunk
    qs = q.reshape(B, n, chunk, H, dh).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(n, chunk)

    # checkpoint each chunk: backward recomputes the (chunk, Sk) score
    # block instead of saving every chunk's softmax residuals — without
    # this, grad-of-map materializes the full S^2 attention matrix
    # (flash-attention-style recompute, in XLA)
    @jax.checkpoint
    def one(args):
        qc, pc = args
        return masked_attention(qc, k, v, pc, k_pos,
                                causal=causal, window=window, scale=scale)

    out = jax.lax.map(one, (qs, ps))  # (n, B, chunk, H, dh)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, dh)


# --- int8 KV quantization (beyond-paper, §Perf H5) -----------------------------

def quantize_kv(x):
    """Per-(batch, position, kv-head) symmetric int8: x (B, S, KV, dh) ->
    (int8 values, f32 scales (B, S, KV))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# --- KV caches ---------------------------------------------------------------

def ring_slot_positions(pos, W: int):
    """Positions held by ring-buffer slots after writes 0..pos-1.

    Slot i holds the latest position p <= pos-1 with p % W == i, or -1 if
    that slot has never been written.
    """
    i = jnp.arange(W)
    last = pos - 1
    p = last - ((last - i) % W)
    return jnp.where((p >= 0) & (p <= last), p, -1)


def cache_write_full(cache_k, cache_v, k, v, pos):
    """Write S new kv entries at [pos, pos+S) of a full cache (B,Smax,KV,dh)."""
    S = k.shape[1]
    idx = (pos + jnp.arange(S)).astype(jnp.int32)
    ck = cache_k.at[:, idx].set(k.astype(cache_k.dtype))
    cv = cache_v.at[:, idx].set(v.astype(cache_v.dtype))
    return ck, cv


def cache_write_ring(cache_k, cache_v, k, v, pos):
    """Write S new entries into a ring cache (B, W, KV, dh) at slots
    (pos+j) % W."""
    W = cache_k.shape[1]
    S = k.shape[1]
    idx = ((pos + jnp.arange(S)) % W).astype(jnp.int32)
    ck = cache_k.at[:, idx].set(k.astype(cache_k.dtype))
    cv = cache_v.at[:, idx].set(v.astype(cache_v.dtype))
    return ck, cv


def decode_attention(q, cache_k, cache_v, pos, *, window: int = 0,
                     ring: bool = False, scale: Optional[float] = None):
    """Single-position decode: q (B, 1, H, dh) against a cache.

    ``pos`` is the absolute position of the query token; the cache holds
    positions < pos (+ the current token is written by the caller before
    calling, so k_pos <= pos are valid).
    """
    if ring:
        W = cache_k.shape[1]
        k_pos = ring_slot_positions(pos + 1, W)
    else:
        Smax = cache_k.shape[1]
        k_pos = jnp.where(jnp.arange(Smax) <= pos, jnp.arange(Smax), -1)
    q_pos = jnp.full((1,), pos, jnp.int32)
    return masked_attention(q, cache_k, cache_v, q_pos, k_pos,
                            causal=True, window=window, scale=scale)
