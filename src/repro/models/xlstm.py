"""xLSTM: alternating mLSTM (matrix memory) and sLSTM (scalar memory) blocks.

24 layers are organized as 12 scanned pair-blocks (mLSTM -> sLSTM), so the
layer scan sees a uniform params structure. Exponential gating with the
log-space max-stabilizer from arXiv:2405.04517. Train/prefill uses the
chunked two-level time scan (outer carries only at chunk boundaries).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamDef, rms_norm

CHUNK = 64


def _dims(cfg: ModelConfig):
    d = cfg.d_model
    dm = int(cfg.mlstm_proj_factor * d)        # mLSTM inner
    H = cfg.n_heads
    dh = dm // H
    dsf = int(cfg.slstm_proj_factor * d)       # sLSTM ffn inner
    return d, dm, H, dh, dsf


def xlstm_param_table(cfg: ModelConfig) -> Dict:
    d, dm, H, dh, dsf = _dims(cfg)
    P = int(cfg.n_layers // 2)  # pair blocks
    mk = lambda *s: ParamDef(s, (None,) * len(s))
    col = lambda *s: ParamDef(s, (None,) * (len(s) - 1) + ("model",))
    row = lambda *s: ParamDef((P,) + s[1:], (None, "model") + (None,) * (len(s) - 2))
    return {
        "emb": ParamDef((cfg.vocab_size, d), ("model", None)),
        "final_norm": ParamDef((d,), (None,), init="ones"),
        "lm_head": ParamDef((d, cfg.vocab_size), (None, "model")),
        "pairs": {
            # mLSTM half
            "m_norm": ParamDef((P, d), (None, None), init="ones"),
            "m_up": col(P, d, 2 * dm),
            "m_q": col(P, dm, dm),
            "m_k": col(P, dm, dm),
            "m_v": col(P, dm, dm),
            "m_ig": mk(P, dm, H),
            "m_fg": mk(P, dm, H),
            "m_out_norm": ParamDef((P, dm), (None, None), init="ones"),
            "m_down": ParamDef((P, dm, d), (None, "model", None)),
            # sLSTM half
            "s_norm": ParamDef((P, d), (None, None), init="ones"),
            "s_w": col(P, d, 4 * d),
            "s_r": mk(P, d, 4 * d),
            "s_ffn_norm": ParamDef((P, d), (None, None), init="ones"),
            "s_up1": col(P, d, dsf),
            "s_up2": col(P, d, dsf),
            "s_down": ParamDef((P, dsf, d), (None, "model", None)),
        },
    }


# --- mLSTM ------------------------------------------------------------------

def _mlstm_step(carry, inputs):
    """carry: C (B,H,dh,dh), n (B,H,dh), m (B,H). inputs q,k,v (B,H,dh),
    ig/fg (B,H) pre-activations (f gate in log space via logsigmoid)."""
    C, n, m, = carry
    q, k, v, ig, fg = inputs
    logf = jax.nn.log_sigmoid(fg)
    m_new = jnp.maximum(logf + m, ig)
    i_p = jnp.exp(ig - m_new)
    f_p = jnp.exp(logf + m - m_new)
    C = f_p[..., None, None] * C + i_p[..., None, None] \
        * (v[..., :, None] * k[..., None, :])
    n = f_p[..., None] * n + i_p[..., None] * k
    h_num = jnp.einsum("bhij,bhj->bhi", C, q)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", n, q)), 1.0)
    h = h_num / denom[..., None]
    return (C, n, m_new), h


def _chunked_time_scan(step, carry, xs, S):
    if S % CHUNK == 0 and S > CHUNK:
        n = S // CHUNK

        @jax.checkpoint
        def chunk_fn(c, cxs):
            return jax.lax.scan(step, c, cxs)

        cxs = jax.tree.map(lambda a: a.reshape(n, CHUNK, *a.shape[1:]), xs)
        carry, ys = jax.lax.scan(chunk_fn, carry, cxs)
        ys = jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), ys)
    else:
        carry, ys = jax.lax.scan(step, carry, xs)
    return carry, ys


def mlstm_apply(cfg: ModelConfig, p, x, state):
    """x (B,S,d); state (C,n,m). Returns (y, new_state)."""
    d, dm, H, dh, _ = _dims(cfg)
    B, S, _ = x.shape
    xn = rms_norm(x, p["m_norm"])
    inner = xn @ p["m_up"]
    xm, z = jnp.split(inner, 2, axis=-1)
    q = (xm @ p["m_q"]).reshape(B, S, H, dh) * dh ** -0.5
    k = (xm @ p["m_k"]).reshape(B, S, H, dh) * dh ** -0.5
    v = (xm @ p["m_v"]).reshape(B, S, H, dh)
    ig = (xm @ p["m_ig"]).astype(jnp.float32)
    fg = (xm @ p["m_fg"]).astype(jnp.float32)

    to_t = lambda a: a.astype(jnp.float32).transpose(1, 0, *range(2, a.ndim))
    xs = (to_t(q), to_t(k), to_t(v), to_t(ig), to_t(fg))
    carry = (state["C"], state["n"], state["m"])
    carry, hs = _chunked_time_scan(_mlstm_step, carry, xs, S)
    state = {"C": carry[0], "n": carry[1], "m": carry[2]}
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, dm).astype(x.dtype)
    h = rms_norm(h, p["m_out_norm"]) * jax.nn.silu(z)
    return x + h @ p["m_down"], state


def mlstm_state(cfg: ModelConfig, batch: int):
    _, dm, H, dh, _ = _dims(cfg)
    z = lambda *s: ((batch,) + s, jnp.float32)
    return {"C": z(H, dh, dh), "n": z(H, dh), "m": z(H)}


# --- sLSTM ------------------------------------------------------------------

def _slstm_step(carry, x_t, r, ds):
    """carry: c,n,m,h (B,ds). x_t (B,4ds) = pre-activations from input."""
    c, n, m, h = carry
    gates = x_t + h @ r
    i, f, z, o = jnp.split(gates, 4, axis=-1)
    logf = jax.nn.log_sigmoid(f)
    m_new = jnp.maximum(logf + m, i)
    i_p = jnp.exp(i - m_new)
    f_p = jnp.exp(logf + m - m_new)
    c = f_p * c + i_p * jnp.tanh(z)
    n = f_p * n + i_p
    h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1.0)
    return (c, n, m_new, h), h


def slstm_apply(cfg: ModelConfig, p, x, state):
    d, _, _, _, dsf = _dims(cfg)
    B, S, _ = x.shape
    xn = rms_norm(x, p["s_norm"])
    pre = (xn @ p["s_w"]).astype(jnp.float32)        # (B,S,4d)
    r = p["s_r"].astype(jnp.float32)
    step = partial(_slstm_step, r=r, ds=d)
    carry = (state["c"], state["n"], state["m"], state["h"])
    carry, hs = _chunked_time_scan(step, carry, pre.transpose(1, 0, 2), S)
    state = dict(zip(("c", "n", "m", "h"), carry))
    h = hs.transpose(1, 0, 2).astype(x.dtype)        # (B,S,d)
    x = x + h
    # gated ffn (proj factor 4/3) on the normed stream: fed the raw
    # residual, the product of two projections squares its scale at
    # every block and a 24-layer stack overflows to inf
    xf = rms_norm(x, p["s_ffn_norm"])
    y = jax.nn.gelu((xf @ p["s_up1"]).astype(jnp.float32)).astype(x.dtype) \
        * (xf @ p["s_up2"])
    return x + y @ p["s_down"], state


def slstm_state(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    return {k: ((batch, d), jnp.float32) for k in ("c", "n", "m", "h")}


# --- pair block ---------------------------------------------------------------

def pair_apply(cfg: ModelConfig, p_pair, x, pair_state):
    x, m_state = mlstm_apply(cfg, p_pair, x, pair_state["m"])
    x, s_state = slstm_apply(cfg, p_pair, x, pair_state["s"])
    return x, {"m": m_state, "s": s_state}


def pair_state_shapes(cfg: ModelConfig, batch: int):
    return {"m": mlstm_state(cfg, batch), "s": slstm_state(cfg, batch)}
