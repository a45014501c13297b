"""Qwen3 dense decoder, as served: RMSNorm before attention and MLP,
grouped-query attention with RMSNorm on each query and key head, rotary
embeddings over the whole head (halves rotated), SwiGLU MLP, a final
RMSNorm and an output head. Plain float32; keys of the configuration file
are those of the published ``config.json``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import mm, rms_norm, round_to


def _dims(c):
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def param_table(c):
    L, d, H, KV, dh, ff, V = _dims(c)
    w = lambda *s: (s, "normal")
    one = lambda *s: (s, "ones")
    table = {
        "emb": w(V, d),
        "final_norm": one(d),
        "layers": {
            "ln1": one(L, d), "ln2": one(L, d),
            "wq": w(L, d, H * dh), "wk": w(L, d, KV * dh),
            "wv": w(L, d, KV * dh), "wo": w(L, H * dh, d),
            "q_norm": one(L, dh), "k_norm": one(L, dh),
            "w1": w(L, d, ff), "w3": w(L, d, ff), "w2": w(L, ff, d),
        },
    }
    if not c["tie_word_embeddings"]:
        table["lm_head"] = w(d, V)
    return table


def _rope(x, theta):
    """x (B, T, heads, dh) at positions 0..T-1."""
    T, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(c, params, tokens, positions, quant=None):
    """Logits (B, len(positions), V) of a causal pass over ``tokens``."""
    L, d, H, KV, dh, ff, V = _dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    B, T = tokens.shape
    G = H // KV
    x = round_to(params["emb"][tokens].astype(jnp.float32), quant, axis=-1)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        xn = rms_norm(x, p["ln1"], eps)
        q = rms_norm(mm(xn, p["wq"], quant).reshape(B, T, H, dh), p["q_norm"], eps)
        k = rms_norm(mm(xn, p["wk"], quant).reshape(B, T, KV, dh), p["k_norm"], eps)
        v = mm(xn, p["wv"], quant).reshape(B, T, KV, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(B, T, KV, G, dh)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                       precision=jax.lax.Precision.HIGHEST) * dh ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + mm(a.reshape(B, T, H * dh), p["wo"], quant)
        xn = rms_norm(x, p["ln2"], eps)
        h = jax.nn.silu(mm(xn, p["w1"], quant)) * mm(xn, p["w3"], quant)
        return x + mm(h, p["w2"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x[:, positions], params["final_norm"].astype(jnp.float32), eps)
    head = params["emb"].T if c["tie_word_embeddings"] else params["lm_head"]
    return mm(x, head.astype(jnp.float32), quant)


def work(c, batch: int, prompt: int, new_tokens: int):
    """Least operations and HBM bytes of one prefill call (``batch`` rows
    of ``prompt`` tokens, logits of the last position only) and of one
    decoded token (its share of a step that reads every weight once for
    the batch; attention over its mean context)."""
    L, d, H, KV, dh, ff, V = _dims(c)
    wb = 2 if c["torch_dtype"] == "bfloat16" else 4
    layer_w = d * H * dh + 2 * d * KV * dh + H * dh * d + 3 * d * ff
    head = d * V
    kv_row = L * 2 * KV * dh * wb              # one position's K and V
    n = batch * prompt
    causal_pairs = prompt * (prompt + 1) // 2
    prefill_flops = (2 * L * layer_w * n
                     + 2 * 2 * L * H * dh * causal_pairs * batch
                     + 2 * head * batch)
    prefill_bytes = ((L * layer_w + head) * wb + n * d * wb + n * kv_row)
    ctx = prompt + (new_tokens + 1) / 2        # mean keys a decoded token sees
    token_flops = 2 * L * layer_w + 2 * 2 * L * H * dh * ctx + 2 * head
    token_bytes = ((L * layer_w + head) * wb / batch + d * wb
                   + ctx * kv_row + kv_row)
    return {"prefill_flops": prefill_flops, "prefill_bytes": prefill_bytes,
            "token_flops": token_flops, "token_bytes": token_bytes}


def program_fields(c):
    """The served ``ModelConfig`` fields that must equal the file's."""
    L, d, H, KV, dh, ff, V = _dims(c)
    return {"family": "dense", "n_layers": L, "d_model": d, "n_heads": H,
            "n_kv_heads": KV, "head_dim": dh, "d_ff": ff, "vocab_size": V,
            "qk_norm": True, "qkv_bias": False, "sliding_window": 0,
            "rope_theta": float(c["rope_theta"]), "n_experts": 0,
            "tie_embeddings": bool(c["tie_word_embeddings"]),
            "param_dtype": c["torch_dtype"], "dtype": c["torch_dtype"]}
