"""xLSTM as served: ``num_blocks`` blocks in (mLSTM, sLSTM) pairs.

mLSTM block: RMSNorm, an up-projection to twice the width split into a
cell input and an output gate, per-head q, k, v and scalar input and
forget gates, the matrix memory with exponential gating and the
log-space stabiliser of arXiv:2405.04517, RMSNorm of the readout times
SiLU of the output gate, a down-projection. sLSTM block: RMSNorm, gate
pre-activations from the input plus a recurrent projection of h, scalar
memory with the same stabiliser, then a GELU-gated FFN on the
RMS-normed stream. Plain float32 with a sequential time scan."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import mm, rms_norm, round_to

EPS = 1e-6


def _dims(c):
    d, H = c["embedding_dim"], c["num_heads"]
    dm = int(c["mlstm_proj_factor"] * d)
    dsf = int(c["slstm_ffn_proj_factor"] * d)
    return c["num_blocks"] // 2, d, dm, H, dm // H, dsf, c["vocab_size"]


def param_table(c):
    P, d, dm, H, dh, dsf, V = _dims(c)
    w = lambda *s: (s, "normal")
    one = lambda *s: (s, "ones")
    return {
        "emb": w(V, d), "final_norm": one(d), "lm_head": w(d, V),
        "pairs": {
            "m_norm": one(P, d), "m_up": w(P, d, 2 * dm),
            "m_q": w(P, dm, dm), "m_k": w(P, dm, dm), "m_v": w(P, dm, dm),
            "m_ig": w(P, dm, H), "m_fg": w(P, dm, H),
            "m_out_norm": one(P, dm), "m_down": w(P, dm, d),
            "s_norm": one(P, d), "s_w": w(P, d, 4 * d), "s_r": w(P, d, 4 * d),
            "s_ffn_norm": one(P, d), "s_up1": w(P, d, dsf),
            "s_up2": w(P, d, dsf), "s_down": w(P, dsf, d),
        },
    }


def _mlstm(c, p, x, quant):
    P, d, dm, H, dh, dsf, V = _dims(c)
    B, T, _ = x.shape
    inner = mm(rms_norm(x, p["m_norm"], EPS), p["m_up"], quant)
    xm, z = inner[..., :dm], inner[..., dm:]
    q = mm(xm, p["m_q"], quant).reshape(B, T, H, dh) * dh ** -0.5
    k = mm(xm, p["m_k"], quant).reshape(B, T, H, dh) * dh ** -0.5
    v = mm(xm, p["m_v"], quant).reshape(B, T, H, dh)
    ig, fg = mm(xm, p["m_ig"], quant), mm(xm, p["m_fg"], quant)

    def step(carry, t):
        C, n, m = carry
        qt, kt, vt, it, ft = t
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        i_p, f_p = jnp.exp(it - m_new), jnp.exp(logf + m - m_new)
        C = f_p[..., None, None] * C + i_p[..., None, None] * (
            vt[..., :, None] * kt[..., None, :])
        n = f_p[..., None] * n + i_p[..., None] * kt
        num = jnp.einsum("bhij,bhj->bhi", C, qt,
                         precision=jax.lax.Precision.HIGHEST)
        den = jnp.maximum(jnp.abs(jnp.sum(n * qt, -1)), 1.0)
        return (C, n, m_new), num / den[..., None]

    f32 = jnp.float32
    zero = (jnp.zeros((B, H, dh, dh), f32), jnp.zeros((B, H, dh), f32),
            jnp.zeros((B, H), f32))
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, h = jax.lax.scan(step, zero, (tm(q), tm(k), tm(v), tm(ig), tm(fg)))
    h = jnp.moveaxis(h, 0, 1).reshape(B, T, dm)
    h = rms_norm(h, p["m_out_norm"], EPS) * jax.nn.silu(z)
    return x + mm(h, p["m_down"], quant)


def _slstm(c, p, x, quant):
    B, T, d = x.shape
    pre = mm(rms_norm(x, p["s_norm"], EPS), p["s_w"], quant)

    def step(carry, g_in):
        cc, n, m, h = carry
        i, f, z, o = jnp.split(g_in + mm(h, p["s_r"], quant), 4, axis=-1)
        logf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(logf + m, i)
        i_p, f_p = jnp.exp(i - m_new), jnp.exp(logf + m - m_new)
        cc = f_p * cc + i_p * jnp.tanh(z)
        n = f_p * n + i_p
        h = jax.nn.sigmoid(o) * cc / jnp.maximum(n, 1.0)
        return (cc, n, m_new, h), h

    zero = tuple(jnp.zeros((B, d), jnp.float32) for _ in range(4))
    _, h = jax.lax.scan(step, zero, jnp.moveaxis(pre, 1, 0))
    x = x + jnp.moveaxis(h, 0, 1)
    xf = rms_norm(x, p["s_ffn_norm"], EPS)
    y = jax.nn.gelu(mm(xf, p["s_up1"], quant)) * mm(xf, p["s_up2"], quant)
    return x + mm(y, p["s_down"], quant)


def forward(c, params, tokens, positions, quant=None):
    """Logits (B, len(positions), V) of a causal pass over ``tokens``."""
    x = round_to(params["emb"][tokens].astype(jnp.float32), quant, axis=-1)

    def pair(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return _slstm(c, p, _mlstm(c, p, x, quant), quant), None

    x, _ = jax.lax.scan(pair, x, params["pairs"])
    x = rms_norm(x[:, positions], params["final_norm"].astype(jnp.float32), EPS)
    return mm(x, params["lm_head"].astype(jnp.float32), quant)


def work(c, batch: int, prompt: int, new_tokens: int):
    """Least operations and HBM bytes of one prefill call (matrix memory
    in its parallel form over the prompt, logits of the last position
    only, final states written once) and of one decoded token (its share
    of a step that reads every weight once for the batch, and its own
    matrix memory read and written in float32)."""
    P, d, dm, H, dh, dsf, V = _dims(c)
    wb = 2 if c["torch_dtype"] == "bfloat16" else 4
    pair_w = (d * 2 * dm + 3 * dm * dm + 2 * dm * H + dm * d
              + 2 * d * 4 * d + 2 * d * dsf + dsf * d)
    head = d * V
    state = P * 4 * (H * dh * dh + H * dh + H + 4 * d)   # f32 state of a row
    n = batch * prompt
    causal_pairs = prompt * (prompt + 1) // 2
    prefill_flops = (2 * P * pair_w * n + 2 * 2 * P * H * dh * causal_pairs
                     * batch + 2 * head * batch)
    prefill_bytes = (P * pair_w + head) * wb + n * d * wb + batch * state
    token_flops = 2 * P * pair_w + 4 * P * H * dh * dh + 2 * head
    token_bytes = (P * pair_w + head) * wb / batch + d * wb + 2 * state
    return {"prefill_flops": prefill_flops, "prefill_bytes": prefill_bytes,
            "token_flops": token_flops, "token_bytes": token_bytes}


def program_fields(c):
    """The served ``ModelConfig`` fields that must equal the file's."""
    return {"family": "ssm", "n_layers": c["num_blocks"],
            "d_model": c["embedding_dim"], "n_heads": c["num_heads"],
            "vocab_size": c["vocab_size"],
            "mlstm_proj_factor": float(c["mlstm_proj_factor"]),
            "slstm_proj_factor": float(c["slstm_ffn_proj_factor"]),
            "tie_embeddings": False,
            "param_dtype": c["torch_dtype"], "dtype": c["torch_dtype"]}
