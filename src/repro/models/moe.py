"""Mixture-of-Experts layer: top-k routing with sort-based dispatch.

Expert-parallel over the ``model`` mesh axis: the (E, C, d) dispatch buffer
is sharded on E, so GSPMD lowers the scatter/gather into all-to-alls —
the communication pattern the paper's "expert" workloads stress.

Dispatch is capacity-bounded (tokens over capacity are dropped, standard
Switch-style), so the active FLOPs match the analytic top-k model instead
of the dense all-experts upper bound.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import ParamDef
from repro.utils.shardctx import current_mesh, maybe_shard


def moe_param_table(cfg: ModelConfig, L: int) -> Dict[str, ParamDef]:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamDef((L, d, E), (None, None, None), dtype="float32"),
        "we1": ParamDef((L, E, d, f), (None, "model", None, None)),
        "we3": ParamDef((L, E, d, f), (None, "model", None, None)),
        "we2": ParamDef((L, E, f, d), (None, "model", None, None)),
    }


def moe_apply(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_load_balance_loss). p holds per-layer slices
    (router (d,E), we1 (E,d,f), ...)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ p["router"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)           # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss.
    me = probs.mean(0)                                # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(
        1.0 / (T * k))
    aux = E * jnp.sum(me * ce)

    # flatten (token, slot) pairs and sort by expert
    flat_e = top_i.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_p = top_p.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sp = flat_e[order], flat_t[order], flat_p[order]

    # position of each entry within its expert bucket
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * k) - offsets[se]

    # Serving steps (small T: decode, short prefill) run DROPLESS
    # (C = T*k) so incremental decoding is exactly consistent with the
    # parallel forward — capacity dropping is batch-dependent and would
    # corrupt the cache semantics. Large-T training/prefill uses the
    # standard Switch capacity bound (drops allowed).
    if T * k <= 4096:
        C = T * k
    else:
        C = int(max(k, -(-T * k // E) * cfg.capacity_factor))
    keep = pos_in_e < C
    dest = jnp.where(keep, se * C + pos_in_e, E * C)  # E*C = drop slot

    buf = jnp.zeros((E * C + 1, d), x.dtype).at[dest].set(xf[st])
    buf = maybe_shard(buf[: E * C].reshape(E, C, d), "model")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["we1"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["we3"])
    out_e = jnp.einsum("ecf,efd->ecd", h, p["we2"])
    out_e = maybe_shard(out_e, "model")

    flat_out = jnp.concatenate(
        [out_e.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0)
    y_sorted = flat_out[dest] * (sp * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((T, d), x.dtype).at[st].add(y_sorted)
    return y.reshape(B, S, d), aux


# --- expert-parallel shard_map path (§Perf H1) --------------------------------
#
# GSPMD cannot shard the sort+scatter dispatch (it replicates the (E*C, d)
# buffer on every chip: 455 GB/dev for qwen3-moe train_4k at baseline).
# The shard_map version keeps activations replicated across the ``model``
# axis, lets every expert shard locally scatter ONLY the tokens routed to
# its own experts, and combines partial outputs with one psum per layer —
# expert parallelism without an all-to-all, with the same routing math as
# ``moe_apply`` (bitwise-identical top-k, so decode consistency holds).

def _local_moe(cfg: ModelConfig, x_l, router, we1, we3, we2, E_l: int,
               repl: bool = False):
    """Per-shard expert computation. ``repl=False``: weights arrive
    pre-sharded on E (E divisible by the axis). ``repl=True`` (E NOT
    divisible — e.g. granite's 40 experts on a 16-way axis): weights
    arrive replicated and each shard dynamic-slices its ceil(E/n) window;
    ownership is masked exactly, so trailing shards idle rather than
    double-count (TPU padding trick, EXPERIMENTS.md §Perf H8)."""
    B_l, S, d = x_l.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B_l * S
    xf = x_l.reshape(T, d)

    logits = xf.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0 / (T * k))
    aux = E * jnp.sum(me * ce)
    aux = jax.lax.pmean(aux, "model")
    for ax in ("data", "pod"):
        try:
            aux = jax.lax.pmean(aux, ax)
        except NameError:
            pass

    flat_e = top_i.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_p = top_p.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sp = flat_e[order], flat_t[order], flat_p[order]
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * k) - offsets[se]

    if T * k <= 4096:
        C = T * k
    else:
        C = int(max(k, -(-T * k // E) * cfg.capacity_factor))
    my0 = jax.lax.axis_index("model") * E_l
    if repl:
        # clamped slice window; ownership mask stays exact
        start = jnp.minimum(my0, max(E - E_l, 0))
        we1 = jax.lax.dynamic_slice_in_dim(we1, start, E_l, axis=0)
        we3 = jax.lax.dynamic_slice_in_dim(we3, start, E_l, axis=0)
        we2 = jax.lax.dynamic_slice_in_dim(we2, start, E_l, axis=0)
    else:
        start = my0
    mine = (se >= my0) & (se < my0 + E_l) & (se < E)
    keep = (pos_in_e < C) & mine
    dest = jnp.where(keep, (se - start) * C + pos_in_e, E_l * C)

    buf = jnp.zeros((E_l * C + 1, d), x_l.dtype).at[dest].set(
        jnp.where(keep[:, None], xf[st], 0))
    buf = buf[: E_l * C].reshape(E_l, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we1)) \
        * jnp.einsum("ecd,edf->ecf", buf, we3)
    out_e = jnp.einsum("ecf,efd->ecd", h, we2)
    flat_out = jnp.concatenate(
        [out_e.reshape(E_l * C, d), jnp.zeros((1, d), x_l.dtype)], axis=0)
    y_sorted = flat_out[dest] * (sp * keep).astype(x_l.dtype)[:, None]
    y = jnp.zeros((T, d), x_l.dtype).at[st].add(y_sorted)
    y = jax.lax.psum(y, "model")
    return y.reshape(B_l, S, d), aux


def moe_apply_ep(cfg: ModelConfig, p, x):
    """Expert-parallel MoE via shard_map. Falls back to ``moe_apply`` when
    no mesh is installed or E is not divisible by the model axis.
    ``REPRO_MOE_EP=0`` forces the GSPMD baseline (paper-faithful §Perf
    baseline runs)."""
    import os
    mesh = current_mesh()
    if os.environ.get("REPRO_MOE_EP", "1") == "0" or mesh is None \
            or "model" not in mesh.shape:
        return moe_apply(cfg, p, x)
    n_model = mesh.shape["model"]
    repl = bool(cfg.n_experts % n_model)
    E_l = -(-cfg.n_experts // n_model)  # ceil: last shards may idle (H8)
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    x_spec = P(dp if x.shape[0] % n_dp == 0 else None, None, None)
    # indivisible E: weights replicated into each shard (small-expert
    # archs only; divisible E keeps weights sharded on E)
    w_spec = P() if repl else P("model", None, None)

    def local(x_l, router, we1, we3, we2):
        y, aux = _local_moe(cfg, x_l, router, we1, we3, we2, E_l,
                            repl=repl)
        if x_spec[0] is None:
            # batch replicated over data axes: make grads/aux consistent
            y = jax.lax.pmean(y, dp)
        return y, aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["we1"], p["we3"], p["we2"])
