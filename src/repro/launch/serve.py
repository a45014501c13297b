"""Serving launcher: run the MQFQ-Sticky control plane.

Two modes:
  --mode sim   (default): discrete-event simulation of a device pool with
               the paper's workloads or the assigned model endpoints.
  --mode real  : real JAX execution of reduced-config endpoints on this
               host's default backend, with the persistent compilation
               cache on (``chip_smoke.py`` serves the published widths).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --policy mqfq-sticky \
      --workload azure --trace-id 4 --d 2
  PYTHONPATH=src python -m repro.launch.serve --mode real \
      --archs qwen3-1.7b,xlstm-350m --requests 20
"""
from __future__ import annotations

import argparse
import json
import random
import time


def run_sim_mode(args) -> dict:
    from repro.server import ServerConfig, make_server
    from repro.workloads.costmodel import endpoint_mix
    from repro.workloads.traces import azure_trace, make_workload, zipf_trace

    if args.workload == "endpoints":
        fns = endpoint_mix(args.endpoint_shape)
        trace = zipf_trace(fns, args.duration, args.rps, seed=args.seed)
    else:
        fns, trace = make_workload(args.workload, n_fns=args.n_fns,
                                   duration=args.duration,
                                   total_rps=args.rps,
                                   trace_id=args.trace_id, seed=args.seed)
    kw = {}
    if args.policy in ("mqfq", "mqfq-sticky"):
        kw = dict(T=args.T, alpha=args.alpha)
    cfg = ServerConfig(policy=args.policy, policy_kwargs=kw,
                       n_devices=args.devices, d=args.d,
                       dynamic_d=args.dynamic_d, mem_policy=args.mem_policy,
                       pool_size=args.pool_size)
    res = make_server(cfg, fns=fns).run_trace(trace)
    out = {
        "policy": args.policy, "events": len(trace),
        "mean_latency_s": round(res.mean_latency(), 3),
        "p99_latency_s": round(res.p99_latency(), 3),
        "cold_pct": round(res.pool.cold_hit_pct, 2),
        "utilization": round(res.mean_utilization(), 3),
        "inter_fn_variance": round(res.inter_fn_variance(), 2),
    }
    print(json.dumps(out, indent=1))
    return out


def run_real_mode(args) -> dict:
    from repro.runtime.device import build_endpoints
    from repro.server import ServerConfig, make_server

    archs = args.archs.split(",")
    endpoints = build_endpoints({a: (a, i) for i, a in enumerate(archs)},
                                kv_quant=args.kv_quant)
    kw = dict(T=args.T, alpha=args.alpha) \
        if args.policy in ("mqfq", "mqfq-sticky") else {}
    # cap residency at roughly half the endpoints (the old engine's
    # max_resident default) so LRU swapping is actually exercised
    max_resident = max(2, len(endpoints) // 2)
    cap = max_resident * max(int(ep.weight_bytes)
                             for ep in endpoints.values())
    cfg = ServerConfig(executor="wallclock", policy=args.policy,
                       policy_kwargs=kw, d=args.d, capacity_bytes=cap)
    server = make_server(cfg, endpoints=endpoints)
    server.start()
    rng = random.Random(args.seed)
    for i in range(args.requests):
        server.submit(rng.choice(archs), {"seed": i})
        time.sleep(args.think_time)
    server.drain(timeout=600)
    res = server.stop()
    lats = [inv.latency for inv in res.invocations]
    out = {
        "policy": args.policy, "completed": len(lats),
        "mean_latency_s": round(sum(lats) / max(len(lats), 1), 3),
        "max_latency_s": round(max(lats, default=0.0), 3),
        "start_types": res.start_type_counts(),
    }
    print(json.dumps(out, indent=1))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="sim", choices=["sim", "real"])
    ap.add_argument("--policy", default="mqfq-sticky")
    ap.add_argument("--T", type=float, default=10.0)
    ap.add_argument("--alpha", type=float, default=2.0)
    ap.add_argument("--d", type=int, default=2)
    ap.add_argument("--dynamic-d", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--mem-policy", default="prefetch_swap")
    ap.add_argument("--pool-size", type=int, default=32)
    ap.add_argument("--workload", default="azure",
                    choices=["azure", "zipf", "endpoints"])
    ap.add_argument("--endpoint-shape", default="decode_32k")
    ap.add_argument("--n-fns", type=int, default=24)
    ap.add_argument("--duration", type=float, default=300.0)
    ap.add_argument("--rps", type=float, default=1.0)
    ap.add_argument("--trace-id", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    # real mode
    ap.add_argument("--archs", default="qwen3-1.7b,xlstm-350m,hymba-1.5b")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--think-time", type=float, default=0.05)
    ap.add_argument("--kv-quant", action="store_true",
                    help="serve with int8 KV caches (§Perf H5)")
    args = ap.parse_args()
    if args.mode == "sim":
        run_sim_mode(args)
    else:
        run_real_mode(args)


if __name__ == "__main__":
    main()
