"""Granite-4.0-H (``granitemoehybrid`` with no routed experts), as served:
``layer_types`` places Mamba-2 and attention layers; every layer is

    h = x + r * mixer(rmsnorm(x)),   x = h + r * mlp(rmsnorm(h))

with ``r`` the ``residual_multiplier``, a SwiGLU MLP of
``shared_intermediate_size``, embeddings times ``embedding_multiplier``
and logits ``rmsnorm(x) @ emb.T / logits_scaling`` (tied embeddings).
Attention: grouped-query, no positional encoding, scores times
``attention_multiplier``. Mamba-2: one fused ``in_proj`` into z, xBC
and dt; a causal conv (with bias) and SiLU over x, B and C together;
per head ``h = exp(dt*A) h + dt x (x) B``, ``y = h C + D x`` with B and C
shared by every head (``mamba_n_groups`` 1), ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the gated RMSNorm
``rmsnorm(y * silu(z)) * w``; ``out_proj``. Plain float32 with a
sequential time scan; keys of the configuration file are those of the
published ``config.json``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import mm, rms_norm, round_to

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(c):
    d, H, KV = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    Hs, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    if c["mamba_n_groups"] != 1 or not c["mamba_conv_bias"]:
        raise NotImplementedError("Mamba-2 served with one group of B and "
                                  "C and a conv bias only")
    di = Hs * P
    assert di == c["mamba_expand"] * d, "mamba heads x head size != expand x d"
    return dict(L=c["num_hidden_layers"], d=d, H=H, KV=KV, dh=d // H,
                ff=c["shared_intermediate_size"], V=c["vocab_size"],
                Hs=Hs, P=P, N=N, di=di, conv=di + 2 * N,
                cw=c["mamba_d_conv"],
                La=c["layer_types"].count("attention"))


def param_table(c):
    k = _dims(c)
    L, d, V, La = k["L"], k["d"], k["V"], k["La"]
    Lm = L - La
    w = lambda *s: (s, "normal")
    one = lambda *s: (s, "ones")
    mamba = {
        "in_proj": w(Lm, d, k["di"] + k["conv"] + k["Hs"]),
        "conv_w": w(Lm, k["cw"], k["conv"]), "conv_b": w(Lm, k["conv"]),
        "dt_bias": w(Lm, k["Hs"]), "a_log": w(Lm, k["Hs"]),
        "d_skip": one(Lm, k["Hs"]), "norm": one(Lm, k["di"]),
        "out_proj": w(Lm, k["di"], d),
    }
    table = {
        "emb": w(V, d), "final_norm": one(d),
        "layers": {"ln1": one(L, d), "ln2": one(L, d),
                   "w1": w(L, d, k["ff"]), "w3": w(L, d, k["ff"]),
                   "w2": w(L, k["ff"], d)},
        "attention": {"wq": w(La, d, k["H"] * k["dh"]),
                      "wk": w(La, d, k["KV"] * k["dh"]),
                      "wv": w(La, d, k["KV"] * k["dh"]),
                      "wo": w(La, k["H"] * k["dh"], d)},
        "mamba": mamba,
    }
    if not c["tie_word_embeddings"]:
        table["lm_head"] = w(d, V)
    return table


def _attention(c, k, p, x, quant):
    B, T, _ = x.shape
    H, KV, dh = k["H"], k["KV"], k["dh"]
    q = mm(x, p["wq"], quant).reshape(B, T, KV, H // KV, dh)
    kk = mm(x, p["wk"], quant).reshape(B, T, KV, dh)
    v = mm(x, p["wv"], quant).reshape(B, T, KV, dh)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(args):                 # one KV head and its query group
        qh, kh, vh = args           # (B,T,G,dh), (B,T,dh), (B,T,dh)
        s = jnp.einsum("bqgd,bsd->bgqs", qh, kh, precision=HIGHEST) \
            * c["attention_multiplier"]
        s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.einsum("bgqs,bsd->bqgd", s, vh, precision=HIGHEST)

    a = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kk, 2, 0),
                           jnp.moveaxis(v, 2, 0)))       # (KV,B,T,G,dh)
    a = jnp.moveaxis(a, 0, 2).reshape(B, T, H * dh)
    return mm(a, p["wo"], quant)


def _mamba(c, k, p, x, quant):
    B, T, _ = x.shape
    Hs, P, N, di, conv = k["Hs"], k["P"], k["N"], k["di"], k["conv"]
    zxbcdt = mm(x, p["in_proj"], quant)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + conv],
                  zxbcdt[..., di + conv:])
    pad = jnp.concatenate([jnp.zeros((B, k["cw"] - 1, conv), jnp.float32),
                           xbc], 1)
    xbc = jax.nn.silu(sum(pad[:, j:j + T] * p["conv_w"][j]
                          for j in range(k["cw"])) + p["conv_b"])
    xs = xbc[..., :di].reshape(B, T, Hs, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (B,T,Hs)
    A = -jnp.exp(p["a_log"])

    def step(h, t):
        xt, dtt, bt, ct = t
        h = (jnp.exp(dtt * A)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=HIGHEST)

    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((B, Hs, P, N), jnp.float32),
                        (tm(xs), tm(dt), tm(Bm), tm(Cm)))
    y = tm(y) + p["d_skip"][:, None] * xs
    y = y.reshape(B, T, di) * jax.nn.silu(z)
    return mm(rms_norm(y, p["norm"], c["rms_norm_eps"]), p["out_proj"], quant)


def _runs(types):
    """(kind, first layer, first of its kind, count) of each run of
    same-kind layers."""
    out, seen = [], {"mamba": 0, "attention": 0}
    for i, t in enumerate(types):
        if out and out[-1][0] == t:
            out[-1][3] += 1
        else:
            out.append([t, i, seen[t], 1])
        seen[t] += 1
    return out


def forward(c, params, tokens, positions, quant=None):
    """Logits (B, len(positions), V) of a causal pass over ``tokens``."""
    k = _dims(c)
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = round_to(params["emb"][tokens].astype(jnp.float32), quant, axis=-1) \
        * c["embedding_multiplier"]
    for kind, i, j, n in _runs(c["layer_types"]):
        mixer = _mamba if kind == "mamba" else _attention

        def layer(x, ij, kind=kind, mixer=mixer):
            # layer i, the j-th of its kind, read from the stacks by index
            pl, pk = f32(jax.tree.map(lambda w: w[ij[0]], params["layers"])), \
                f32(jax.tree.map(lambda w: w[ij[1]], params[kind]))
            h = x + r * mixer(c, k, pk, rms_norm(x, pl["ln1"], eps), quant)
            xn = rms_norm(h, pl["ln2"], eps)
            y = jax.nn.silu(mm(xn, pl["w1"], quant)) * mm(xn, pl["w3"], quant)
            return h + r * mm(y, pl["w2"], quant), None

        x, _ = jax.lax.scan(layer, x, (jnp.arange(i, i + n),
                                       jnp.arange(j, j + n)))
    x = rms_norm(x[:, positions], params["final_norm"].astype(jnp.float32),
                 eps)
    head = params["emb"].T if c["tie_word_embeddings"] else params["lm_head"]
    return mm(x, head.astype(jnp.float32), quant) / c["logits_scaling"]


def work(c, batch: int, prompt: int, new_tokens: int):
    """Least operations and HBM bytes of one prefill call (``batch`` rows
    of ``prompt`` tokens, logits of the last position only, every state
    written once) and of one decoded token (its share of a step that
    reads every weight once for the batch; attention over its mean
    context; its own SSM and conv state read and written once)."""
    k = _dims(c)
    L, La, d, V = k["L"], k["La"], k["d"], k["V"]
    Lm = L - La
    Hs, P, N, di, conv, cw = (k["Hs"], k["P"], k["N"], k["di"], k["conv"],
                              k["cw"])
    wb = 2 if c["torch_dtype"] == "bfloat16" else 4
    attn_w = 2 * d * k["H"] * k["dh"] + 2 * d * k["KV"] * k["dh"]
    mamba_w = d * (di + conv + Hs) + di * d
    mlp_w = 3 * d * k["ff"]
    small = Lm * (conv * (cw + 1) + 3 * Hs + di) + 2 * L * d + d  # vectors
    head = d * V
    weights = La * attn_w + Lm * mamba_w + L * mlp_w + head
    kv_row = La * 2 * k["KV"] * k["dh"] * wb       # one position's K and V
    # per row: SSM state (f32) and conv state (cw - 1 positions)
    state = Lm * (Hs * P * N * 4 + (cw - 1) * conv * wb)
    # per token per Mamba layer: conv, dt*x (x) B, decay and update, y = hC
    ssm_flops = Lm * (2 * conv * cw + 4 * Hs * P * N + 2 * Hs * P * N)
    n = batch * prompt
    causal_pairs = prompt * (prompt + 1) // 2
    prefill_flops = (2 * (weights - head) * n + ssm_flops * n
                     + 2 * 2 * La * k["H"] * k["dh"] * causal_pairs * batch
                     + 2 * head * batch)
    prefill_bytes = ((weights + small) * wb + n * d * wb + n * kv_row
                     + batch * state)
    ctx = prompt + (new_tokens + 1) / 2   # mean keys a decoded token sees
    token_flops = (2 * (weights - head) + ssm_flops
                   + 2 * 2 * La * k["H"] * k["dh"] * ctx + 2 * head)
    token_bytes = ((weights + small) * wb / batch + d * wb
                   + ctx * kv_row + kv_row + 2 * state)
    return {"prefill_flops": prefill_flops, "prefill_bytes": prefill_bytes,
            "token_flops": token_flops, "token_bytes": token_bytes}


def program_fields(c):
    """The served ``ModelConfig`` fields that must equal the file's."""
    k = _dims(c)
    return {"family": "mamba2", "n_layers": k["L"], "d_model": k["d"],
            "n_heads": k["H"], "n_kv_heads": k["KV"], "head_dim": k["dh"],
            "d_ff": k["ff"], "vocab_size": k["V"],
            "layer_types": tuple(c["layer_types"]),
            "ssm_heads": k["Hs"], "ssm_head_dim": k["P"],
            "ssm_state": k["N"], "conv_width": k["cw"],
            "rope": c["position_embedding_type"] != "nope",
            "qk_norm": False, "qkv_bias": bool(c["attention_bias"]),
            "sliding_window": 0, "n_experts": c["num_local_experts"],
            "embedding_multiplier": float(c["embedding_multiplier"]),
            "residual_multiplier": float(c["residual_multiplier"]),
            "attention_multiplier": float(c["attention_multiplier"]),
            "logits_scaling": float(c["logits_scaling"]),
            "norm_eps": float(c["rms_norm_eps"]),
            "tie_embeddings": bool(c["tie_word_embeddings"]),
            "param_dtype": c["torch_dtype"], "dtype": c["torch_dtype"]}
