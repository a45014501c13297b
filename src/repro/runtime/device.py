"""Real-execution endpoints: the JAX device path.

A ``JaxEndpoint`` is one serveable function: a model (a reduced config
on the CPU test rig, published widths on a TPU), host-resident weights
(numpy), and jitted prefill/decode executables. The memory manager's
abstract "regions" map to real bytes here:

  cold       — build + compile + upload   (first instantiation)
  host_warm  — weights evicted from device: re-upload only
  warm       — device-resident: execute immediately

Residency is per device: ``upload(dev_id)`` puts one copy of the weights
on ``jax.devices()[dev_id]``, the chip the control plane placed the
invocation on, and ``evict(dev_id)`` drops only that copy. Uploads and
evictions are real operations with real cost on every backend, so the
control-plane integration is exercised end to end.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from pathlib import Path
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import CachePlan, Model, build_model, decode_cache_plan
from repro.models.model import cache_bytes
from repro.shapes import InputShape

# fixed, so that one checkout's runs find each other's compiles
# (the cache is keyed by path); listed in .gitignore
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def _greedy(logits):
    """The greedy token of each row, as a ``(batch, 1)`` int32 column."""
    return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path:
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else ``CACHE_DIR``.
    Every compile is kept however short it was, since a cold start pays
    for each one it misses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def served_programs(model: Model, plan: CachePlan,
                    decode_steps: int) -> Dict[str, Any]:
    """The jitted programs an endpoint serves with: ``prefill`` (batch
    to last logits and cache), ``decode`` (one step) and ``decode_loop``
    (prefill's logits and cache to ``decode_steps`` greedy tokens)."""

    def _prefill(params, batch):
        return model.prefill_fn(params, batch, cache_len=plan.length,
                                ring=plan.ring)

    def _decode(params, cache, tok, pos):
        return model.decode_fn(params, cache, tok, pos, ring=plan.ring)

    def _decode_loop(params, cache, logits, pos):
        """Greedy decoding on the device: the prefill's argmax, then
        ``decode_steps`` decode steps, each fed the previous argmax.
        Returns the ``(batch, decode_steps)`` tokens and the last
        logits."""
        def step(i, carry):
            cache, tok, toks, _ = carry
            logits, cache = _decode(params, cache, tok, pos + i)
            tok = _greedy(logits)
            toks = jax.lax.dynamic_update_slice(toks, tok, (0, i))
            return cache, tok, toks, logits

        toks = jnp.zeros((logits.shape[0], decode_steps), jnp.int32)
        carry = (cache, _greedy(logits), toks, logits)
        _, _, toks, logits = jax.lax.fori_loop(0, decode_steps, step, carry)
        return toks, logits

    return {"prefill": jax.jit(_prefill), "decode": jax.jit(_decode),
            "decode_loop": jax.jit(_decode_loop)}


class JaxEndpoint:
    def __init__(self, fn_id: str, cfg: ModelConfig, seed: int = 0,
                 serve_seq: int = 64, serve_batch: int = 2,
                 decode_steps: int = 4):
        self.fn_id = fn_id
        self.cfg = cfg
        self.model = build_model(cfg)
        self.serve_shape = InputShape("serve", serve_seq, serve_batch,
                                      "prefill")
        self.decode_steps = decode_steps
        # room for the prompt and every decoded token: a full cache of
        # serve_seq slots would clamp the first decode write onto the
        # prompt's last slot
        self.plan = decode_cache_plan(cfg, serve_seq + decode_steps)
        rng = jax.random.PRNGKey(seed)
        # host weights: numpy (host RAM). One jitted program draws them
        # all: run eagerly, every leaf shape compiles its own sampler
        params = jax.jit(self.model.init_params)(rng)
        self.host_params = jax.tree.map(np.asarray, params)
        self.weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        self.device_params: Dict[int, Any] = {}   # dev_id -> weights
        self._compiled: Dict[str, Any] = {}
        self.lock = threading.Lock()  # one instance: serialize executions

    # -- residency ---------------------------------------------------------
    def resident_on(self, dev_id: int = 0) -> bool:
        return dev_id in self.device_params

    def upload(self, dev_id: int = 0) -> float:
        t0 = time.monotonic()
        params = jax.device_put(self.host_params, jax.devices()[dev_id])
        jax.block_until_ready(params)
        self.device_params[dev_id] = params
        return time.monotonic() - t0

    def evict(self, dev_id: int = 0) -> None:
        self.device_params.pop(dev_id, None)

    # -- compilation (the "container init" analogue) -------------------------
    def compile(self, dev_id: int = 0) -> float:
        """Trace and compile the two served programs, prefill and the
        decode loop (with the persistent cache on, a repeat finds both
        there), uploading first if needed. The single decode step
        behind ``decode`` compiles on its first call."""
        t0 = time.monotonic()
        compiled = served_programs(self.model, self.plan, self.decode_steps)
        if not self.resident_on(dev_id):
            self.upload(dev_id)
        params = self.device_params[dev_id]
        # trigger compilation with abstract-matching dummy batch
        with jax.default_device(jax.devices()[dev_id]):
            batch = self.model.make_batch(self.serve_shape)
            logits, cache = compiled["prefill"](params, batch)
            toks, _ = compiled["decode_loop"](params, cache, logits,
                                              self.prompt_len(batch))
        jax.block_until_ready(toks)
        self._compiled = compiled  # publish atomically: compiled only when usable
        return time.monotonic() - t0

    @property
    def compiled(self) -> bool:
        return bool(self._compiled)

    # -- serving -----------------------------------------------------------
    def prompt_len(self, batch: dict) -> int:
        """Position of the first decoded token (VLM patches come first)."""
        return batch["tokens"].shape[1] + (
            self.cfg.n_patches if self.cfg.family == "vlm" else 0)

    def prefill(self, batch: dict, dev_id: int = 0):
        """Compiled prefill on device ``dev_id``: (last logits, cache)."""
        return self._compiled["prefill"](self.device_params[dev_id], batch)

    def decode(self, cache, tok, pos, dev_id: int = 0):
        """One compiled decode step on device ``dev_id``."""
        return self._compiled["decode"](self.device_params[dev_id], cache,
                                        tok, pos)

    def execute(self, request: Optional[dict] = None,
                dev_id: int = 0) -> Dict[str, Any]:
        """One batched request on device ``dev_id``: prefill, then the
        greedy decode loop as one device program, whose tokens are the
        one read back. ``device`` is where the logits were made,
        ``weight_devices`` where the weights it read live;
        ``device_wait_s`` is the time spent blocked on that read and
        ``host_syncs`` the number of blocking reads; ``kv_bytes`` and
        ``state_bytes`` are the bytes of attention KV and of recurrent
        state in the cache the decode loop carried (0 for a kind the
        model lacks)."""
        assert self.resident_on(dev_id) and self.compiled
        t0 = time.monotonic()
        with jax.default_device(jax.devices()[dev_id]):
            batch = self.model.make_batch(
                self.serve_shape,
                rng=jax.random.PRNGKey((request or {}).get("seed", 0)))
        params = self.device_params[dev_id]
        logits, cache = self.prefill(batch, dev_id)
        toks, logits = self._compiled["decode_loop"](
            params, cache, logits, self.prompt_len(batch))
        t = time.monotonic()
        tokens = np.array(toks)          # a writable copy, as the caller owns it
        waited = time.monotonic() - t
        return {"exec_s": time.monotonic() - t0,
                "device_wait_s": waited,
                "host_syncs": 1,
                **cache_bytes(cache),
                "tokens": tokens,
                "device": next(iter(logits.devices())),
                "weight_devices": {d for leaf in jax.tree.leaves(params)
                                   for d in leaf.devices()}}


def build_endpoints(fns: Mapping[str, Tuple[str, int]], *,
                    full_width: bool = False, kv_quant: bool = False,
                    **endpoint_kw) -> Dict[str, JaxEndpoint]:
    """One ``JaxEndpoint`` per ``fn_id -> (arch, seed)``: the published
    config with ``full_width`` (bf16 weights), else its reduced smoke
    variant. Turns on the persistent compilation cache first."""
    configure_compile_cache()
    out = {}
    for fn_id, (arch, seed) in fns.items():
        cfg = get_config(arch)
        if not full_width:
            cfg = cfg.reduced()
        if kv_quant:
            cfg = dataclasses.replace(cfg, kv_quant=True)
        out[fn_id] = JaxEndpoint(fn_id, cfg, seed=seed, **endpoint_kw)
    return out
