"""Selective-state-space (Mamba-style) heads: Hymba's SSM branch (family
hybrid) and the Mamba-2 mixer (family mamba2).

Per-head scalar decay A, state size N (=cfg.ssm_state), depthwise causal
conv front-end. Training/prefill uses a two-level chunked time scan (outer
carry = state at chunk boundaries, inner steps rematerialized) so reverse-
mode does not checkpoint every timestep.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamDef

CHUNK = 64


def ssm_param_table(cfg: ModelConfig, L: int) -> Dict[str, ParamDef]:
    d = cfg.d_model
    Hs, Ps, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di = Hs * Ps
    cw = cfg.conv_width
    return {
        "in_proj": ParamDef((L, d, di), (None, None, "model")),
        "conv_w": ParamDef((L, cw, di), (None, None, "model"), init="normal",
                           scale=cw ** -0.5),
        "dt_proj": ParamDef((L, d, Hs), (None, None, None)),
        "dt_bias": ParamDef((L, Hs), (None, None), init="zeros"),
        "b_proj": ParamDef((L, d, N), (None, None, None)),
        "c_proj": ParamDef((L, d, N), (None, None, None)),
        "a_log": ParamDef((L, Hs), (None, None), init="zeros"),
        "d_skip": ParamDef((L, Hs), (None, None), init="ones"),
        "out_proj": ParamDef((L, di, d), (None, "model", None)),
    }


def causal_conv(xin, conv_state, w):
    """xin (B,S,di), conv_state (B,cw-1,di), w (cw,di).
    out[t] = sum_j w[j] * xp[t+j] with xp = [state, xin]."""
    cw = w.shape[0]
    S = xin.shape[1]
    xp = jnp.concatenate([conv_state.astype(xin.dtype), xin], axis=1)
    out = sum(xp[:, j:j + S] * w[j] for j in range(cw))
    return out, xp[:, -(cw - 1):]


def _ssm_step(state, inputs, A):
    """state (B,Hs,P,N); inputs: x_t (B,Hs,P), dt (B,Hs), Bt/Ct (B,N)."""
    x_t, dt, Bt, Ct = inputs
    decay = jnp.exp(dt * A)                                   # (B,Hs)
    upd = (dt[..., None] * x_t)[..., None] * Bt[:, None, None, :]
    state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, Ct)
    return state, y


def _time_scan(state, xs, A):
    """``_ssm_step`` over the leading (time) axis of ``xs``: ys (S,B,Hs,P).
    A multiple of CHUNK steps scans by chunks, each rematerialized under
    reverse mode."""
    S = xs[0].shape[0]
    step = partial(_ssm_step, A=A)
    if S % CHUNK == 0 and S > CHUNK:
        n = S // CHUNK

        @jax.checkpoint
        def chunk_fn(st, chunk_xs):
            return jax.lax.scan(step, st, chunk_xs)

        cxs = jax.tree.map(
            lambda a: a.reshape(n, CHUNK, *a.shape[1:]), xs)
        state, ys = jax.lax.scan(chunk_fn, state, cxs)
        return state, ys.reshape(S, *ys.shape[2:])
    return jax.lax.scan(step, state, xs)


def ssm_apply_seq(cfg: ModelConfig, p, x, state, conv_state):
    """Full-sequence (train/prefill). x (B,S,d) -> y (B,S,d), new states."""
    B, S, d = x.shape
    Hs, Ps = cfg.ssm_heads, cfg.ssm_head_dim
    xin = x @ p["in_proj"]
    xc, new_conv = causal_conv(xin, conv_state, p["conv_w"])
    xc = jax.nn.silu(xc).reshape(B, S, Hs, Ps)
    dt = jax.nn.softplus((x @ p["dt_proj"]) + p["dt_bias"]).astype(jnp.float32)
    Bt = (x @ p["b_proj"]).astype(jnp.float32)
    Ct = (x @ p["c_proj"]).astype(jnp.float32)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))

    xs = (xc.astype(jnp.float32).transpose(1, 0, 2, 3),
          dt.transpose(1, 0, 2), Bt.transpose(1, 0, 2), Ct.transpose(1, 0, 2))
    state, ys = _time_scan(state, xs, A)
    y = ys.transpose(1, 0, 2, 3)                     # (B,S,Hs,P)
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * xc.astype(jnp.float32)
    y = y.astype(x.dtype).reshape(B, S, Hs * Ps)
    return y @ p["out_proj"], state, new_conv


def ssm_apply_decode(cfg: ModelConfig, p, x, state, conv_state):
    """Single-token decode. x (B,1,d)."""
    y, state, new_conv = ssm_apply_seq(cfg, p, x, state, conv_state)
    return y, state, new_conv


def ssm_state_shapes(cfg: ModelConfig, batch: int):
    Hs, Ps, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return {
        "ssm_state": ((batch, Hs, Ps, N), jnp.float32),
        "conv_state": ((batch, cfg.conv_width - 1, Hs * Ps),
                       cfg.compute_dtype),
    }


# --- Mamba-2 mixer (family mamba2) ---------------------------------------------

def mamba2_dims(cfg: ModelConfig):
    """(heads, head size, state size, inner width, conv channels: x, then
    one group's B and C)."""
    Hs, Ps, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di = Hs * Ps
    return Hs, Ps, N, di, di + 2 * N


def mamba2_param_table(cfg: ModelConfig, L: int) -> Dict[str, ParamDef]:
    d = cfg.d_model
    Hs, Ps, N, di, conv = mamba2_dims(cfg)
    return {
        # one fused projection into z, xBC and dt
        "in_proj": ParamDef((L, d, di + conv + Hs), (None, None, "model")),
        "conv_w": ParamDef((L, cfg.conv_width, conv), (None, None, "model")),
        "conv_b": ParamDef((L, conv), (None, "model")),
        "dt_bias": ParamDef((L, Hs), (None, None)),
        "a_log": ParamDef((L, Hs), (None, None)),
        "d_skip": ParamDef((L, Hs), (None, None), init="ones"),
        "norm": ParamDef((L, di), (None, None), init="ones"),
        "out_proj": ParamDef((L, di, d), (None, "model", None)),
    }


def mamba2_apply(cfg: ModelConfig, p, x, ssm_state, conv_state):
    """Mamba-2 over x (B,S,d) from the given states: in_proj to z, xBC
    and dt; a causal conv (with bias) and SiLU over x, B and C together;
    the recurrence ``h = exp(dt*A) h + dt x (x) B``, ``y = h C + D x``
    with B and C shared by every head (one group), in float32; the gated
    RMSNorm ``rmsnorm(y * silu(z)) * w``; out_proj. Returns (y, new ssm
    state, new conv state)."""
    B, S, _ = x.shape
    Hs, Ps, N, di, conv = mamba2_dims(cfg)
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv], axis=-1)
    xbc, new_conv = causal_conv(xbc, conv_state, p["conv_w"])
    xbc = jax.nn.silu(xbc + p["conv_b"])
    xh, Bt, Ct = jnp.split(xbc.astype(jnp.float32), [di, di + N], axis=-1)
    xh = xh.reshape(B, S, Hs, Ps)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    state, ys = _time_scan(ssm_state, (tm(xh), tm(dt), tm(Bt), tm(Ct)), A)
    y = tm(ys) + p["d_skip"].astype(jnp.float32)[:, None] * xh
    y = y.reshape(B, S, di) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = (y * jax.lax.rsqrt(var + cfg.norm_eps)).astype(x.dtype) * p["norm"]
    return y @ p["out_proj"], state, new_conv


def mamba2_state_shapes(cfg: ModelConfig, batch: int):
    Hs, Ps, N, di, conv = mamba2_dims(cfg)
    return {
        "ssm": ((batch, Hs, Ps, N), jnp.float32),
        "conv": ((batch, cfg.conv_width - 1, conv), cfg.compute_dtype),
    }
