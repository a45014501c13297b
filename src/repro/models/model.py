"""Unified model API over all architecture families.

``build_model(cfg)`` returns a ``Model`` exposing:
  - parameter views (abstract / initialized / partition specs)
  - loss_fn(params, batch)                       (training)
  - prefill_fn(params, batch)                    (prompt -> cache)
  - decode_fn(params, cache, tokens, pos)        (serve_step)
  - cache/batch shape planning per assigned input shape
  - ``cache_bytes(cache)``: a serve cache's bytes by kind (KV or state)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, transformer, whisper, xlstm_stack
from repro.shapes import InputShape


@dataclass(frozen=True)
class CachePlan:
    kind: str        # "full" | "ring" | "state"
    length: int      # kv slots (0 for pure-state archs)

    @property
    def ring(self) -> bool:
        return self.kind == "ring"


# cache leaves that hold attention keys and values (and their int8 scales)
KV_LEAVES = frozenset({"k", "v", "ck", "cv", "k_scale", "v_scale",
                       "ck_scale", "cv_scale"})


def cache_bytes(cache) -> Dict[str, int]:
    """Bytes of a serve cache by kind: ``kv_bytes`` (attention keys and
    values, with their scales) and ``state_bytes`` (recurrent and conv
    state: every other leaf)."""
    out = {"kv_bytes": 0, "state_bytes": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        kv = any(getattr(k, "key", None) in KV_LEAVES for k in path)
        out["kv_bytes" if kv else "state_bytes"] += leaf.nbytes
    return out


def decode_cache_plan(cfg: ModelConfig, seq_len: int) -> CachePlan:
    if cfg.family == "ssm":
        return CachePlan("state", 0)
    if cfg.sliding_window:
        w = min(cfg.sliding_window, seq_len)
        return CachePlan("ring", w)
    if seq_len > 65_536:
        # beyond-paper sub-quadratic variant for dense archs (DESIGN.md)
        return CachePlan("ring", cfg.long_context_window)
    return CachePlan("full", seq_len)


@dataclass
class Model:
    cfg: ModelConfig
    param_table: Any
    _loss: Callable
    _prefill: Callable
    _decode: Callable
    _cache_shapes: Callable  # (batch, length, ring) -> {name: (shape, dtype)}

    # -- parameter views ------------------------------------------------
    def abstract_params(self):
        return common.abstract_params(self.param_table, self.cfg)

    def init_params(self, rng):
        return common.init_params(self.param_table, self.cfg, rng)

    def partition_specs(self, mesh):
        return common.partition_specs(self.param_table, mesh)

    # -- steps ------------------------------------------------------------
    def loss_fn(self, params, batch):
        return self._loss(params, batch)

    def prefill_fn(self, params, batch, cache_len=None, ring=False):
        return self._prefill(params, batch, cache_len, ring)

    def decode_fn(self, params, cache, tokens, pos, ring=False):
        return self._decode(params, cache, tokens, pos, ring)

    # -- shapes -----------------------------------------------------------
    def cache_shapes(self, batch: int, plan: CachePlan):
        return self._cache_shapes(batch, plan.length, plan.ring)

    def zero_cache(self, batch: int, plan: CachePlan, abstract=False):
        sh = self.cache_shapes(batch, plan)
        leaf = lambda x: isinstance(x, tuple) and len(x) == 2 \
            and isinstance(x[0], tuple)
        mk = (lambda sd: jax.ShapeDtypeStruct(*sd)) if abstract \
            else (lambda sd: jnp.zeros(*sd))
        return jax.tree.map(mk, sh, is_leaf=leaf)

    def batch_shapes(self, shape: InputShape) -> Dict[str, Tuple]:
        """Input array shapes/dtypes for a given assigned input shape."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        itok = jnp.int32
        if shape.kind == "decode":
            return {"tokens": ((B, 1), itok)}
        out: Dict[str, Tuple] = {}
        s_text = S
        if cfg.family == "vlm":
            s_text = S - cfg.n_patches
            out["patch_embeds"] = ((B, cfg.n_patches, cfg.d_model),
                                   cfg.compute_dtype)
        if cfg.family == "audio":
            out["frames"] = ((B, cfg.encoder_len, cfg.d_model),
                             cfg.compute_dtype)
        out["tokens"] = ((B, s_text), itok)
        if shape.kind == "train":
            out["labels"] = ((B, s_text), itok)
        return out

    def make_batch(self, shape: InputShape, rng=None, abstract=False):
        shapes = self.batch_shapes(shape)
        if abstract:
            return {k: jax.ShapeDtypeStruct(s, d)
                    for k, (s, d) in shapes.items()}
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        out = {}
        for k, (s, d) in shapes.items():
            rng, sub = jax.random.split(rng)
            if jnp.issubdtype(d, jnp.integer):
                out[k] = jax.random.randint(sub, s, 0, self.cfg.vocab_size,
                                            dtype=d)
            else:
                out[k] = (jax.random.normal(sub, s, jnp.float32) * 0.02
                          ).astype(d)
        return out


# --- family wiring -------------------------------------------------------------

def _tf_loss(cfg):
    def loss(params, batch):
        pe = batch.get("patch_embeds")
        logits, aux = transformer.forward(cfg, params, batch["tokens"],
                                          patch_embeds=pe)
        if pe is not None:
            logits = logits[:, pe.shape[1]:]
        ce = common.cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
        return ce + 0.01 * aux, {"ce": ce, "aux": aux}
    return loss


def _tf_prefill(cfg):
    def f(params, batch, cache_len, ring):
        return transformer.prefill(cfg, params, batch["tokens"],
                                   patch_embeds=batch.get("patch_embeds"),
                                   cache_len=cache_len, ring=ring)
    return f


def _tf_decode(cfg):
    def f(params, cache, tokens, pos, ring):
        return transformer.decode_step(cfg, params, cache, tokens, pos,
                                       ring=ring)
    return f


def _whisper_loss(cfg):
    def loss(params, batch):
        logits, aux = whisper.forward(cfg, params, batch["tokens"],
                                      batch["frames"])
        ce = common.cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
        return ce, {"ce": ce, "aux": aux}
    return loss


def _xlstm_loss(cfg):
    def loss(params, batch):
        logits, aux = xlstm_stack.forward(cfg, params, batch["tokens"])
        ce = common.cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
        return ce, {"ce": ce, "aux": aux}
    return loss


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "ssm":
        return Model(
            cfg, xlstm_stack.param_table(cfg),
            _xlstm_loss(cfg),
            lambda p, b, cl, ring: xlstm_stack.prefill(cfg, p, b["tokens"]),
            lambda p, c, t, pos, ring: xlstm_stack.decode_step(
                cfg, p, c, t, pos),
            lambda batch, length, ring: xlstm_stack.state_shapes(cfg, batch),
        )
    if cfg.family == "audio":
        return Model(
            cfg, whisper.whisper_param_table(cfg),
            _whisper_loss(cfg),
            lambda p, b, cl, ring: whisper.prefill(cfg, p, b["tokens"],
                                                   b["frames"], cl),
            lambda p, c, t, pos, ring: whisper.decode_step(cfg, p, c, t, pos),
            lambda batch, length, ring: whisper.cache_shapes(
                cfg, batch, length),
        )
    return Model(
        cfg, transformer.decoder_param_table(cfg),
        _tf_loss(cfg),
        _tf_prefill(cfg),
        _tf_decode(cfg),
        lambda batch, length, ring: transformer.cache_shapes(
            cfg, batch, length, ring),
    )
