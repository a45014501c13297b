"""Decoder-only transformer stack: dense / MoE / hybrid(attn+SSM) / VLM,
and Mamba-2 + attention layers in a fixed pattern (mamba2).

Layers are stacked on a leading L dim and scanned (compile time is depth-
independent). Modes:
  - train:   teacher-forced full sequence, remat per block
  - prefill: full sequence, returns KV cache (full or ring)
  - decode:  one token against the cache (serve_step)
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ParamDef, rms_norm, rope
from repro.utils.shardctx import batch_axis, maybe_shard

PREFILL_CHUNK = 1024


def _attn_param_table(cfg: ModelConfig, L: int) -> Dict[str, ParamDef]:
    d, dh = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    layers: Dict[str, ParamDef] = {
        "wq": ParamDef((L, d, H * dh), (None, None, "model")),
        "wk": ParamDef((L, d, KV * dh), (None, None, "model")),
        "wv": ParamDef((L, d, KV * dh), (None, None, "model")),
        "wo": ParamDef((L, H * dh, d), (None, "model", None)),
    }
    if cfg.qkv_bias:
        layers["bq"] = ParamDef((L, H * dh), (None, "model"), init="zeros")
        layers["bk"] = ParamDef((L, KV * dh), (None, "model"), init="zeros")
        layers["bv"] = ParamDef((L, KV * dh), (None, "model"), init="zeros")
    if cfg.qk_norm:
        layers["q_norm"] = ParamDef((L, dh), (None, None), init="ones")
        layers["k_norm"] = ParamDef((L, dh), (None, None), init="ones")
    return layers


def decoder_param_table(cfg: ModelConfig) -> Dict:
    d, L = cfg.d_model, cfg.n_layers
    layers: Dict[str, ParamDef] = {
        "ln1": ParamDef((L, d), (None, None), init="ones"),
        "ln2": ParamDef((L, d), (None, None), init="ones"),
    }
    if not cfg.layer_types:
        layers.update(_attn_param_table(cfg, L))
    if cfg.is_moe:
        layers.update(moe_mod.moe_param_table(cfg, L))
    else:
        layers["w1"] = ParamDef((L, d, cfg.d_ff), (None, None, "model"))
        layers["w3"] = ParamDef((L, d, cfg.d_ff), (None, None, "model"))
        layers["w2"] = ParamDef((L, cfg.d_ff, d), (None, "model", None))
    if cfg.family == "hybrid":
        layers.update(ssm_mod.ssm_param_table(cfg, L))
        layers["attn_out_norm"] = ParamDef((L, d), (None, None), init="ones")
        layers["ssm_out_norm"] = ParamDef((L, d), (None, None), init="ones")
    table = {
        "emb": ParamDef((cfg.vocab_size, d), ("model", None)),
        "layers": layers,
        "final_norm": ParamDef((d,), (None,), init="ones"),
    }
    if cfg.layer_types:
        # each kind's mixers on a stack of their own; norms and MLPs in
        # ``layers``, one per layer
        table["attention"] = _attn_param_table(
            cfg, cfg.n_attention_layers)
        table["mamba"] = ssm_mod.mamba2_param_table(
            cfg, cfg.n_layers - cfg.n_attention_layers)
    if not cfg.tie_embeddings:
        table["lm_head"] = ParamDef((d, cfg.vocab_size), (None, "model"))
    return table


# --- single block -------------------------------------------------------------


def _qkv(cfg: ModelConfig, p, xn, positions):
    B, S, _ = xn.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = xn @ p["wq"]
    k = xn @ p["wk"]
    v = xn @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KV, dh)
    v = v.reshape(B, S, KV, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta, partial=cfg.rope_2d)
        k = rope(k, positions, cfg.rope_theta, partial=cfg.rope_2d)
    return q, k, v


def _mlp(cfg: ModelConfig, p, x):
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    h = maybe_shard(h, batch_axis(), None, "model")
    return h @ p["w2"]


def _attn_branch(cfg: ModelConfig, p, xn, layer_cache, pos, mode,
                 ring: bool):
    B, S, _ = xn.shape
    window = cfg.sliding_window
    scale = cfg.attention_multiplier or None
    if mode == "train":
        positions = jnp.arange(S)
        q, k, v = _qkv(cfg, p, xn, positions)
        chunk = PREFILL_CHUNK if S > 2 * PREFILL_CHUNK else 0
        if chunk:
            out = attn.chunked_attention(q, k, v, positions, positions,
                                         causal=True, window=window,
                                         chunk=chunk, scale=scale)
        else:
            out = attn.masked_attention(q, k, v, positions, positions,
                                        causal=True, window=window,
                                        scale=scale)
        new_cache = None
    elif mode == "prefill":
        positions = jnp.arange(S)
        q, k, v = _qkv(cfg, p, xn, positions)
        chunk = PREFILL_CHUNK if S > 2 * PREFILL_CHUNK else 0
        if chunk:
            out = attn.chunked_attention(q, k, v, positions, positions,
                                         causal=True, window=window,
                                         chunk=chunk, scale=scale)
        else:
            out = attn.masked_attention(q, k, v, positions, positions,
                                        causal=True, window=window,
                                        scale=scale)
        ck, cv = layer_cache["k"], layer_cache["v"]
        if cfg.kv_quant:
            k, sk = attn.quantize_kv(k)
            v, sv = attn.quantize_kv(v)
        if ring:
            W = ck.shape[1]
            tail = min(S, W)
            ck, cv = attn.cache_write_ring(
                ck, cv, k[:, S - tail:], v[:, S - tail:], S - tail)
            if cfg.kv_quant:
                cks, cvs = attn.cache_write_ring(
                    layer_cache["k_scale"], layer_cache["v_scale"],
                    sk[:, S - tail:], sv[:, S - tail:], S - tail)
        else:
            ck, cv = attn.cache_write_full(ck, cv, k, v, 0)
            if cfg.kv_quant:
                cks, cvs = attn.cache_write_full(
                    layer_cache["k_scale"], layer_cache["v_scale"],
                    sk, sv, 0)
        new_cache = {"k": ck, "v": cv}
        if cfg.kv_quant:
            new_cache.update(k_scale=cks, v_scale=cvs)
    else:  # decode
        positions = jnp.full((1,), pos, jnp.int32)
        q, k, v = _qkv(cfg, p, xn, positions)
        ck, cv = layer_cache["k"], layer_cache["v"]
        if cfg.kv_quant:
            k, sk = attn.quantize_kv(k)
            v, sv = attn.quantize_kv(v)
        idx = (pos % ck.shape[1]) if ring else pos
        ck = jax.lax.dynamic_update_slice_in_dim(
            ck, k.astype(ck.dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cv, v.astype(cv.dtype), idx, axis=1)
        new_cache = {"k": ck, "v": cv}
        if cfg.kv_quant:
            cks = jax.lax.dynamic_update_slice_in_dim(
                layer_cache["k_scale"], sk, idx, axis=1)
            cvs = jax.lax.dynamic_update_slice_in_dim(
                layer_cache["v_scale"], sv, idx, axis=1)
            new_cache.update(k_scale=cks, v_scale=cvs)
            # dequantize at the read: XLA fuses convert*scale into the
            # attention dots, so HBM traffic is the int8 bytes (§Perf H5)
            ck = attn.dequantize_kv(ck, cks, cfg.compute_dtype)
            cv = attn.dequantize_kv(cv, cvs, cfg.compute_dtype)
        out = attn.decode_attention(q, ck, cv, pos, window=window, ring=ring,
                                    scale=scale)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = maybe_shard(out, batch_axis(), None, "model")
    return out @ p["wo"], new_cache


def block_apply(cfg: ModelConfig, p, x, layer_cache, pos, mode,
                ring: bool):
    """One decoder block. Returns (x, new_layer_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    xn = rms_norm(x, p["ln1"])
    attn_out, new_attn_cache = _attn_branch(
        cfg, p, xn, layer_cache, pos, mode, ring)
    new_cache: Dict[str, Any] = dict(new_attn_cache or {})
    if cfg.family == "hybrid":
        if mode == "train":
            B = x.shape[0]
            st = ssm_mod.ssm_state_shapes(cfg, B)
            ssm_state = jnp.zeros(*st["ssm_state"])
            conv_state = jnp.zeros(*st["conv_state"])
        else:
            ssm_state = layer_cache["ssm_state"]
            conv_state = layer_cache["conv_state"]
        ssm_out, ssm_state, conv_state = ssm_mod.ssm_apply_seq(
            cfg, p, xn, ssm_state, conv_state)
        x = x + 0.5 * (rms_norm(attn_out, p["attn_out_norm"])
                       + rms_norm(ssm_out, p["ssm_out_norm"]))
        if mode != "train":
            new_cache["ssm_state"] = ssm_state
            new_cache["conv_state"] = conv_state
    else:
        x = x + attn_out
    xn2 = rms_norm(x, p["ln2"])
    if cfg.is_moe:
        ffn_out, aux = moe_mod.moe_apply_ep(cfg, p, xn2)
    else:
        ffn_out = _mlp(cfg, p, xn2)
    x = x + ffn_out
    return x, (new_cache if mode != "train" else None), aux


# --- Mamba-2 + attention pattern (mamba2) ----------------------------------------

def layer_runs(layer_types: Tuple[str, ...]):
    """The shortest period that repeats to the whole pattern, as
    (number of periods, ((kind, count), ...) runs of one period)."""
    L = len(layer_types)
    p = next(p for p in range(1, L + 1)
             if L % p == 0 and layer_types == layer_types[:p] * (L // p))
    runs = []
    for kind in layer_types[:p]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return L // p, tuple((k, n) for k, n in runs)


def _pattern_layer(cfg: ModelConfig, params, kind: str, pos, mode: str,
                   ring: bool, carry, idx):
    """Layer ``i`` of the pattern, the ``j``-th of its kind:
    ``h = x + r * mixer(rmsnorm(x))``, ``x = h + r * mlp(rmsnorm(h))``.
    It reads its weights and cache from the stacks by index and writes
    its cache back in place."""
    x, cache = carry
    i, j = idx
    at = lambda tree, k: jax.tree.map(lambda a: a[k], tree)
    pl, pk = at(params["layers"], i), at(params[kind], j)
    c = None if cache is None else at(cache[kind], j)
    r, eps = cfg.residual_multiplier, cfg.norm_eps
    xn = rms_norm(x, pl["ln1"], eps)
    if kind == "mamba":
        if c is None:       # train: from zero state
            c = jax.tree.map(lambda sd: jnp.zeros(*sd),
                             ssm_mod.mamba2_state_shapes(cfg, x.shape[0]),
                             is_leaf=_is_shape)
        y, ssm, conv = ssm_mod.mamba2_apply(cfg, pk, xn, c["ssm"], c["conv"])
        c = {"ssm": ssm, "conv": conv}
    else:
        y, c = _attn_branch(cfg, pk, xn, c, pos, mode, ring)
    h = x + r * y
    x = h + r * _mlp(cfg, pl, rms_norm(h, pl["ln2"], eps))
    if cache is not None:
        cache = dict(cache, **{kind: jax.tree.map(
            lambda a, b: a.at[j].set(b.astype(a.dtype)), cache[kind], c)})
    return (x, cache), None


def pattern_stack(cfg: ModelConfig, params, x, cache, pos, mode: str,
                  ring: bool):
    """``x`` through the layers of ``cfg.layer_types``: one scan over the
    pattern's periods and, in each, one scan per run of same-kind
    layers, so compile time does not grow with depth. Returns (x, cache);
    ``cache`` is None in train mode."""
    n_per, runs = layer_runs(cfg.layer_types)
    period = sum(n for _, n in runs)
    per_kind = {k: sum(n for kk, n in runs if kk == k) for k, _ in runs}

    def one_period(carry, p):
        i0, done = 0, dict.fromkeys(per_kind, 0)
        for kind, n in runs:
            body = partial(_pattern_layer, cfg, params, kind, pos, mode, ring)
            if mode == "train":
                body = jax.checkpoint(body)
            idx = (p * period + i0 + jnp.arange(n),
                   p * per_kind[kind] + done[kind] + jnp.arange(n))
            carry, _ = jax.lax.scan(body, carry, idx)
            i0, done[kind] = i0 + n, done[kind] + n
        return carry, None

    (x, cache), _ = jax.lax.scan(one_period, (x, cache), jnp.arange(n_per))
    return x, cache


# --- cache --------------------------------------------------------------------

def _kv_shapes(cfg: ModelConfig, L: int, batch: int, cache_len: int) -> Dict:
    KV, dh = cfg.n_kv_heads, cfg.head_dim
    dt = jnp.int8 if cfg.kv_quant else cfg.compute_dtype
    shapes = {
        "k": ((L, batch, cache_len, KV, dh), dt),
        "v": ((L, batch, cache_len, KV, dh), dt),
    }
    if cfg.kv_quant:
        shapes["k_scale"] = ((L, batch, cache_len, KV), jnp.float32)
        shapes["v_scale"] = ((L, batch, cache_len, KV), jnp.float32)
    return shapes


def cache_shapes(cfg: ModelConfig, batch: int, cache_len: int,
                 ring: bool) -> Dict:
    """Shapes/dtypes of the serve cache (leading dim L on every leaf).
    A Mamba-2 + attention stack keeps two groups: ``attention`` (KV of
    its attention layers) and ``mamba`` (conv and SSM state of the
    others)."""
    L = cfg.n_layers
    if cfg.layer_types:
        La = cfg.n_attention_layers
        states = ssm_mod.mamba2_state_shapes(cfg, batch)
        return {"attention": _kv_shapes(cfg, La, batch, cache_len),
                "mamba": {name: ((L - La,) + s, d)
                          for name, (s, d) in states.items()}}
    shapes = _kv_shapes(cfg, L, batch, cache_len)
    if cfg.family == "hybrid":
        st = ssm_mod.ssm_state_shapes(cfg, batch)
        for name, (s, d) in st.items():
            shapes[name] = ((L,) + s, d)
    return shapes


def zero_cache(cfg: ModelConfig, batch: int, cache_len: int, ring: bool,
               abstract: bool = False):
    shapes = cache_shapes(cfg, batch, cache_len, ring)
    mk = jax.ShapeDtypeStruct if abstract else jnp.zeros
    return jax.tree.map(lambda sd: mk(*sd), shapes, is_leaf=_is_shape)


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


# --- full stack ----------------------------------------------------------------

def _embed(cfg: ModelConfig, params, tokens, patch_embeds=None):
    x = params["emb"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    return maybe_shard(x.astype(cfg.compute_dtype), batch_axis())


def _unembed(cfg: ModelConfig, params, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["emb"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return maybe_shard(logits, batch_axis(), None, "model")


def forward(cfg: ModelConfig, params, tokens, patch_embeds=None):
    """Teacher-forced logits over the full sequence (training)."""
    x = _embed(cfg, params, tokens, patch_embeds)
    if cfg.layer_types:
        x, _ = pattern_stack(cfg, params, x, None, 0, "train", False)
        return _unembed(cfg, params, x), jnp.zeros((), jnp.float32)

    block = partial(block_apply, cfg, mode="train", pos=0, ring=False,
                    layer_cache=None)

    @jax.checkpoint
    def scan_body(carry, p_layer):
        x, aux = carry
        # sequence-parallel carry: the rematerialization checkpoint saved
        # per layer is (B, S/model, d) instead of (B, S, d) — GSPMD
        # all-gathers S inside the block where attention needs it
        x = maybe_shard(x, batch_axis(), "model")
        x, _, a = block(p_layer, x)
        x = maybe_shard(x, batch_axis(), "model")
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return _unembed(cfg, params, x), aux


def prefill(cfg: ModelConfig, params, tokens, patch_embeds=None,
            cache_len: Optional[int] = None, ring: bool = False):
    """Run the prompt, return (last-position logits, serve cache)."""
    x = _embed(cfg, params, tokens, patch_embeds)
    B, S, _ = x.shape
    cache_len = cache_len or S
    cache = zero_cache(cfg, B, cache_len, ring)

    def scan_body(x, xs):
        p_layer, layer_cache = xs
        x = maybe_shard(x, batch_axis(), "model")  # sequence-parallel carry
        x, new_cache, _ = block_apply(cfg, p_layer, x, layer_cache, 0,
                                      "prefill", ring)
        return x, new_cache

    if cfg.layer_types:
        x, cache = pattern_stack(cfg, params, x, cache, 0, "prefill", ring)
    else:
        x, cache = jax.lax.scan(scan_body, x, (params["layers"], cache))
    logits = _unembed(cfg, params, x[:, -1:])
    return logits[:, 0], cache


def decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                ring: bool = False):
    """One serve step: tokens (B,1) at absolute position ``pos``."""
    x = _embed(cfg, params, tokens)

    def scan_body(x, xs):
        p_layer, layer_cache = xs
        x, new_cache, _ = block_apply(cfg, p_layer, x, layer_cache, pos,
                                      "decode", ring)
        return x, new_cache

    if cfg.layer_types:
        x, cache = pattern_stack(cfg, params, x, cache, pos, "decode", ring)
    else:
        x, cache = jax.lax.scan(scan_body, x, (params["layers"], cache))
    logits = _unembed(cfg, params, x)
    return logits[:, 0], cache
