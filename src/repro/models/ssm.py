"""Selective-state-space (Mamba-style) heads: Hymba's SSM branch (family
hybrid) and the Mamba-2 mixer (family mamba2).

Per-head scalar decay A, state size N (=cfg.ssm_state), one group of B
and C shared by every head, depthwise causal conv front-end. The time
recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``,
``y_t = h_t C_t`` runs in float32 by sequence length: one ``_ssm_step``
for a single position (every decode step), and the chunked SSD form
(state space duality, Mamba-2 paper §6) for more (train and prefill):
matmuls inside chunks of ``CHUNK`` positions, and one decay matmul
across the chunk states, with no loop over time.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamDef

# positions per chunk of the SSD form (chip measurement, PERF.md §5)
CHUNK = 128


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


def _segsum(a):
    """a (..., T) -> (..., T, T): ``out[i, j] = a[j+1] + ... + a[i]`` for
    i >= j, -inf above the diagonal: a masked sum of the terms, not a
    difference of long cumsums, so it stays exact where the decay is
    strong; the sum is one float32 matmul with a triangle of ones."""
    T = a.shape[-1]
    ones = jnp.ones((T, T), a.dtype)
    x = a[..., :, None] * jnp.tril(ones, -1)         # x[k, j] = a[k], k > j
    x = jnp.einsum("ik,...kj->...ij", jnp.tril(ones), x,
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.where(jnp.tril(ones) > 0, x, -jnp.inf)


def _ssd_scan(state, xs, A):
    """The recurrence of ``_ssm_step`` over the leading (time) axis of
    ``xs`` in the chunked SSD form: (final state, ys (S,B,Hs,P)). S is
    padded up to a multiple of the chunk with dt = 0 and x = B = C = 0,
    so a padded position neither decays nor updates the state."""
    x, dt, Bm, Cm = xs
    S = x.shape[0]
    Q = min(CHUNK, S)
    nc = -(-S // Q)
    pad = nc * Q - S
    chunks = lambda v: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)
                               ).reshape(nc, Q, *v.shape[1:])
    x, dt, Bm, Cm = map(chunks, (x, dt, Bm, Cm))      # (c,l,b,...)
    ein = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    a = dt * A                                        # (c,l,b,h)
    a_cum = jnp.cumsum(a, axis=1)
    xdt = x * dt[..., None]                           # (c,l,b,h,p)
    # inside each chunk: ((C B^T) o L) @ (dt x), C B^T shared by heads
    decay = jnp.exp(_segsum(jnp.moveaxis(a, 1, -1)))  # L: (c,b,h,l,s)
    cb = ein("clbn,csbn->cbls", Cm, Bm)
    y = ein("cbhls,csbhp->clbhp", cb[:, :, None] * decay, xdt)
    # each chunk's end state from zero (L's last row decays a position
    # to the chunk's end), then chunk to chunk from `state`
    to_end = jnp.moveaxis(decay[..., -1, :], -1, 1)  # (c,l,b,h)
    ends = ein("clbhp,clbn->cbhpn", xdt * to_end[..., None], Bm)
    ends = jnp.concatenate([state[None], ends])       # (c+1,b,h,p,n)
    a_chunk = jnp.pad(jnp.moveaxis(a_cum[:, -1], 0, -1), [(0, 0), (0, 0),
                                                           (1, 0)])
    across = jnp.exp(_segsum(a_chunk))                # (b,h,c+1,c+1)
    starts = ein("bhzc,cbhpn->zbhpn", across, ends)
    # from each chunk's start state
    y = y + ein("clbn,cbhpn->clbhp", Cm, starts[:-1]) \
        * jnp.exp(a_cum)[..., None]
    return starts[-1], y.reshape(nc * Q, *y.shape[2:])[:S]


def _time_scan(state, xs, A):
    """The recurrence over the leading (time) axis of ``xs``: (final
    state, ys (S,B,Hs,P)). One ``_ssm_step`` for S == 1 (decode); the
    chunked SSD form otherwise."""
    if xs[0].shape[0] == 1:
        state, y = _ssm_step(state, [v[0] for v in xs], A)
        return state, y[None]
    return _ssd_scan(state, xs, A)


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
