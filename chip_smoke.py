#!/usr/bin/env python3
"""Chip smoke test: serve full-width models through the MQFQ-Sticky
wall-clock server on a TPU, and check what comes back.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # placement across a four-chip host

One chip: qwen3-1.7b and xlstm-350m at their published widths (bf16,
random weights from fixed seeds) are served through
``make_server(ServerConfig(executor="wallclock", policy="mqfq-sticky"))``
under an HBM budget that holds either model alone but not both, so the
run has cold, warm and host-warm starts. Then each endpoint's compiled
prefill and teacher-forced decode steps are checked against the full
forward pass on the same device weights, and each endpoint is compiled
a second time to show whether the persistent compilation cache served it.

Four chips: two seeds of each model are served with ``n_devices=4``;
every chip must be used, each invocation must run on the chip MQFQ-Sticky
placed it on, and the tokens must equal those of the same requests
served on one chip in the same process. Nothing else runs.

Exits non-zero, and prints no result, when JAX finds no TPU. The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCHS = ("qwen3-1.7b", "xlstm-350m")
# one chip: alternate and repeat, one request at a time, so that the
# run holds cold, warm and host-warm starts whatever the timing
SEQUENCE = ("qwen3-1.7b", "qwen3-1.7b", "xlstm-350m", "xlstm-350m",
            "qwen3-1.7b", "xlstm-350m", "qwen3-1.7b", "qwen3-1.7b")
# teacher-forced check. The served path and the full forward pass both
# round to bf16; if each lies within e of f32 arithmetic, they lie within
# 2e of each other, with e measured as the forward's own distance from an
# f32 run on the same weights. A wrong position or a lost cache write
# moves logits by about their whole spread, far beyond that.
ERR_FACTOR = 2.0
# random weights leave top logits closer than bf16 rounding, so some
# argmax flips are expected; a misaligned position agrees ~1/vocab
ARGMAX_MIN = 0.5          # least share of positions with equal argmax


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found only {devs[0].platform} devices; this "
             f"script measures the chip and has no CPU fallback")
    if len(devs) < n_chips:
        fail(f"--chips {n_chips} needs {n_chips} TPU chips, JAX found "
             f"{len(devs)}")
    return devs


class CacheEvents:
    """Counts JAX's persistent compilation cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build(fns):
    from repro.runtime.device import build_endpoints
    eps = {}
    for fn_id, spec in fns.items():
        t0 = time.monotonic()
        eps.update(build_endpoints({fn_id: spec}, full_width=True))
        ep = eps[fn_id]
        print(f"endpoint init {fn_id}: {time.monotonic() - t0:.3f} s "
              f"({ep.weight_bytes} weight bytes, {ep.cfg.n_layers} layers, "
              f"d_model {ep.cfg.d_model}, {ep.cfg.param_dtype})")
    return eps


def serve(eps, requests, *, n_devices: int, capacity_bytes: int,
          one_at_a_time: bool):
    """Serve ``requests`` [(fn_id, seed)] through the wall-clock server;
    returns the RunResult after checking that nothing failed."""
    from repro.server import ServerConfig, make_server
    cfg = ServerConfig(executor="wallclock", policy="mqfq-sticky",
                       n_devices=n_devices, d=1,
                       capacity_bytes=capacity_bytes)
    server = make_server(cfg, endpoints=eps)
    server.start()
    try:
        for fn_id, seed in requests:
            server.submit(fn_id, {"seed": seed})
            if one_at_a_time:
                server.drain(timeout=900)
        server.drain(timeout=900)
    finally:
        res = server.stop()
    invs = sorted(res.invocations, key=lambda i: i.inv_id)
    if len(invs) != len(requests) or not all(i.done for i in invs):
        fail(f"{len(invs)} of {len(requests)} invocations completed")
    if res.failed_count:
        fail(f"{res.failed_count} invocations failed")
    for inv in invs:
        toks = inv.output["tokens"]
        vocab = eps[inv.fn_id].cfg.vocab_size
        print(f"  inv {inv.inv_id} {inv.fn_id} seed {inv.request['seed']}: "
              f"{inv.start_type} on device {inv.device_id}, "
              f"overhead {inv.overhead:.3f} s, execute "
              f"{inv.service_time:.3f} s, tokens {toks.tolist()}")
        if toks.min() < 0 or toks.max() >= vocab:
            fail(f"{inv.fn_id}: token outside [0, {vocab})")
    return res, invs


def logit_check(ep, dev_id: int = 0, steps: int = 4) -> None:
    """Compiled prefill + ``steps`` teacher-forced decode steps against
    the full forward pass over the same tokens and device weights, with
    that forward's own bf16 rounding (against f32 arithmetic on the same
    weights) as the yardstick."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.models import transformer, xlstm_stack

    cfg = ep.cfg
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    fwd = xlstm_stack.forward if cfg.family == "ssm" else transformer.forward
    B, S = ep.serve_shape.global_batch, ep.serve_shape.seq_len
    steps = min(steps, ep.decode_steps)
    if not ep.resident_on(dev_id):
        ep.upload(dev_id)
    params = ep.device_params[dev_id]
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_device(jax.devices()[dev_id]):
        tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S + steps),
                                    0, cfg.vocab_size, dtype=jnp.int32)
        ref = f32(jax.jit(lambda p, t: fwd(cfg, p, t)[0])(params, tokens))
        with jax.default_matmul_precision("highest"):
            exact = jax.jit(lambda p, t: fwd(
                cfg32, jax.tree.map(f32, p), t)[0])(params, tokens)
        logits, cache = ep.prefill({"tokens": tokens[:, :S]}, dev_id)
        got = [logits]
        for t in range(S, S + steps):
            logits, cache = ep.decode(cache, tokens[:, t:t + 1], t, dev_id)
            got.append(logits)
    got = jnp.stack([f32(g) for g in got], axis=1)
    want, exact = ref[:, S - 1:S + steps], exact[:, S - 1:S + steps]
    maxabs = lambda a, b: float(jnp.max(jnp.abs(a - b)))
    err, floor = maxabs(got, want), maxabs(want, exact)
    spread = float(jnp.max(want) - jnp.min(want))
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    print(f"logit check {ep.fn_id}: max abs error vs the bf16 forward "
          f"{err:.6f} (limit {ERR_FACTOR} x {floor:.6f}, the bf16 "
          f"forward's max abs error vs f32 arithmetic; served vs f32 "
          f"{maxabs(got, exact):.6f}; logit spread {spread:.6f}), argmax "
          f"agreement {agree:.3f} over {B * (steps + 1)} positions "
          f"(limit {ARGMAX_MIN})")
    if not (err <= ERR_FACTOR * floor and agree >= ARGMAX_MIN):
        fail(f"{ep.fn_id}: served logits disagree with the forward pass")


def one_chip(devs) -> None:
    cache = CacheEvents()
    eps = build({a: (a, i) for i, a in enumerate(ARCHS)})
    weights = sorted(ep.weight_bytes for ep in eps.values())
    # the largest model fits alone, the two together do not
    cap = weights[-1] + weights[0] // 2
    assert weights[-1] <= cap < sum(weights)
    print(f"serving {len(SEQUENCE)} requests, HBM budget {cap} bytes")
    res, invs = serve(eps, [(fn, i) for i, fn in enumerate(SEQUENCE)],
                      n_devices=1, capacity_bytes=cap, one_at_a_time=True)
    counts = res.start_type_counts()
    print(f"start types: {json.dumps(counts, sort_keys=True)}")
    if not all(counts.get(t, 0) for t in ("cold", "warm", "host_warm")):
        fail(f"expected cold, warm and host_warm starts, got {counts}")
    for inv in invs:
        if inv.start_type == "cold":
            print(f"cold start {inv.fn_id} (compile + upload): "
                  f"{inv.overhead:.3f} s")
    print_peak(devs[0], "after serving")
    for fn_id, ep in eps.items():
        ep.evict(0)
        up = ep.upload(0)
        print(f"upload {fn_id}: {up:.3f} s "
              f"({ep.weight_bytes / up / 1e9:.3f} GB/s host to HBM)")
        h0, m0 = cache.hits, cache.misses
        t = ep.compile(0)
        print(f"second compile {fn_id}: {t:.3f} s, persistent cache "
              f"hits {cache.hits - h0}, misses {cache.misses - m0} "
              f"({'hit' if cache.hits > h0 else 'no hit'})")
        logit_check(ep)
        ep.evict(0)
    print_peak(devs[0], "after the logit checks (f32 reference included)")


def print_peak(dev, when: str) -> None:
    stats = dev.memory_stats() or {}
    print(f"peak HBM bytes in use {when} (memory_stats "
          f"peak_bytes_in_use): "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")


def four_chips(devs, n: int) -> None:
    fns = {f"{a}/s{s}": (a, s) for a in ARCHS for s in (0, 1)}
    eps = build(fns)
    requests = [(fn, i) for i, fn in enumerate(list(fns) * 2)]
    print(f"reference: {len(requests)} requests on one chip")
    _, ref = serve(eps, requests, n_devices=1, capacity_bytes=16 * 2**30,
                   one_at_a_time=False)
    ref_tokens = {(i.fn_id, i.request["seed"]): i.output["tokens"]
                  for i in ref}
    for ep in eps.values():
        ep.evict(0)
    print(f"placement: the same requests with n_devices={n}")
    _, invs = serve(eps, requests, n_devices=n, capacity_bytes=16 * 2**30,
                    one_at_a_time=False)
    used = sorted({i.device_id for i in invs})
    print(f"chips used: {used}")
    if used != list(range(n)):
        fail(f"expected dispatches on every chip 0..{n - 1}, got {used}")
    for inv in invs:
        want, out = devs[inv.device_id], inv.output
        if out["device"] != want or out["weight_devices"] != {want}:
            fail(f"inv {inv.inv_id} {inv.fn_id}: placed on {want}, ran on "
                 f"{out['device']}, weights on "
                 f"{sorted(map(str, out['weight_devices']))}")
        if not (inv.output["tokens"]
                == ref_tokens[(inv.fn_id, inv.request["seed"])]).all():
            fail(f"inv {inv.inv_id} {inv.fn_id}: tokens differ from the "
                 f"one-chip run")
    print(f"placement check: {len(invs)} invocations ran on their placed "
          f"chip with their weights there, tokens equal to one chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip placement phase")
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    import jax
    print(f"device_kind: {devs[0].device_kind}")
    print(f"device_count: {len(devs)}")
    print(f"jax_version: {jax.__version__}")
    if args.chips == 1:
        one_chip(devs)
    else:
        four_chips(devs, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
