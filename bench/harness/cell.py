"""One run of one cell: set-up, the open-loop window, the check, the
metrics. ``run`` returns the object the result line prints."""
from __future__ import annotations

import gc
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from harness import check, spec, tracing
from harness import traffic as tr
from harness.context import Context, Record

DRAIN_S = 120.0


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices(chips: int):
    """The accelerator chips JAX finds; ``NoChip`` if there are fewer
    than ``chips`` or none."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"no accelerator: JAX found only {devs[0].platform} "
                     f"devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Traces and backend compiles JAX reports while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, _secs: float, **_kw) -> None:
        if not self.on:
            return
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def _program_differs(fam, config, eps) -> List[str]:
    want = fam.program_fields(config)
    bad = []
    for fid, ep in eps.items():
        for k, v in want.items():
            got = getattr(ep.cfg, k)
            if got != v:
                bad.append(f"{fid}: {k} is {got!r}, the file says {v!r}")
    return bad


class Bench:
    """One process's served stack for a cell: ``setup`` builds the
    endpoints and the server and warms every function; ``window`` drives
    one open-loop window through it; ``close`` frees the program's state
    so that the reference can run."""

    def __init__(self, cell: spec.Cell, *, t_start: float, devs,
                 bytes_limit: Optional[int] = None):
        self.cell, self.t_start, self.devs = cell, t_start, devs
        self.bytes_limit = bytes_limit
        conf = cell.config
        self.req = conf["request"]
        self.fids = tr.function_ids(cell.config_name, cell.traffic)
        self.seeds = cell.traffic["functions"]["weight_seeds"]

    def setup(self, warm_seed: int, traced: bool) -> None:
        import jax
        sys.path.insert(0, os.path.join(spec.ROOT, "src"))
        import reference
        from repro.runtime.device import build_endpoints
        from repro.server import ServerConfig, make_server

        conf, traf, req = self.cell.config, self.cell.traffic, self.req
        self.fam = reference.family(conf["reference"])
        t0 = time.monotonic()
        log(f"set-up: JAX and the chip took {t0 - self.t_start:.3f} s")
        prog = conf["program"]
        eps = build_endpoints(
            {f: (prog["arch"], s) for f, s in zip(self.fids, self.seeds)},
            full_width=prog["full_width"], serve_seq=req["prompt"],
            serve_batch=req["batch"], decode_steps=req["new_tokens"])
        t1 = time.monotonic()
        log(f"set-up: {len(eps)} endpoints initialised in {t1 - t0:.3f} s")
        self.differs = _program_differs(self.fam, conf, eps)
        for line in self.differs:
            log(f"config mismatch: {line}")
        limit = self.bytes_limit
        if limit is None:
            limit = int(self.devs[0].memory_stats()["bytes_limit"])
        weights = [ep.weight_bytes for ep in eps.values()]
        srv = traf["server"]
        # room for one function beyond the budget: the executor skips
        # evicting a function that is mid-execution, so HBM can briefly
        # hold one more than the control plane counts
        capacity = limit - max(weights) - conf["exec_reserve_bytes"]
        log(f"HBM budget {capacity} bytes (bytes_limit {limit} less the "
            f"largest function {max(weights)} and "
            f"{conf['exec_reserve_bytes']} for execution); {len(weights)} "
            f"functions of {sum(weights)} bytes; the budget holds "
            f"{int(capacity // max(weights))}")
        self.eps = eps
        self.server = make_server(ServerConfig(
            executor="wallclock", policy=srv["policy"], d=srv["d"],
            n_devices=self.cell.chips, capacity_bytes=capacity,
            mem_policy=srv["mem_policy"]), endpoints=eps)
        tracing.instrument(eps, self.server.control)
        self.server.start()
        rng = tr.rng_for(warm_seed, stream=2)
        for f in self.fids:
            self.server.submit(f, {"seed": int(rng.integers(0, 2**31 - 1))})
            self.server.drain(timeout=900)
        if traced:      # the profiler's own first start, off the window
            scratch = tempfile.mkdtemp(prefix="bench-warm-trace-")
            tracing.start(scratch)
            tracing.stop()
            shutil.rmtree(scratch, ignore_errors=True)
        self.counter = CompileCounter()
        log(f"set-up: compile, upload and warm-up of {len(self.fids)} "
            f"functions took {time.monotonic() - t1:.3f} s")

    def window(self, arrivals: List[tr.Arrival], seconds: float,
               trace_dir: Optional[str] = None, traced: bool = False):
        """Drive ``arrivals`` open-loop and wait until all are served.
        Returns (records, served, the window's (open, close), trace)."""
        import jax
        server, fids = self.server, self.fids
        origin = time.monotonic() + 0.2

        def submit(a: tr.Arrival):
            with jax.profiler.TraceAnnotation("feeder"):
                return server.submit(fids[a.fn], {"seed": a.request_seed})

        feeder = tr.Feeder(arrivals, origin, submit)
        self.counter.on = True
        feeder.start()
        trace = None
        if traced:
            tcfg = self.cell.traffic["trace"]
            log_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            _sleep_until(origin + tcfg["start_share"] * seconds)
            tracing.start(log_dir)
            _sleep_until(min(time.monotonic() + tcfg["seconds"],
                             origin + seconds))
            tracing.stop()
        feeder.join(timeout=seconds + DRAIN_S)
        if feeder.is_alive() or feeder.error is not None:
            raise RuntimeError(f"feeder failed: {feeder.error!r}")
        server.drain(timeout=DRAIN_S)
        self.counter.on = False
        if traced:
            trace = tracing.reduce(tracing.find_trace(log_dir))
            if trace_dir is None:
                shutil.rmtree(log_dir, ignore_errors=True)
        records, served = [], []
        for r in feeder.released:
            inv = r.inv
            records.append(Record(
                fn=r.arrival.fn, due=r.due, release=r.release,
                completion=None if inv.completion is None
                else inv.completion + r.offset,
                queue_time=None if inv.dispatch_time is None
                else inv.queue_time,
                overhead=inv.overhead, service_time=inv.service_time,
                start_type=inv.start_type, failed=inv.failed))
            if inv.completion is not None and not inv.failed:
                served.append(check.Served(
                    r.arrival.fn, self.seeds[r.arrival.fn],
                    r.arrival.request_seed, inv.output["tokens"],
                    inv.start_type))
        self.released = feeder.released
        return records, served, (origin, origin + seconds), trace

    def stop(self) -> int:
        """Stop the server; the number of window invocations that did not
        complete exactly once."""
        res = self.server.stop()
        ids = [inv.inv_id for inv in res.invocations]
        window = {r.inv.inv_id for r in self.released}
        done = [i for i in ids if i in window]
        return (len(window) - len(set(done))) + (len(done) - len(set(done)))

    def memory_peak(self) -> int:
        stats = [d.memory_stats() or {} for d in self.devs[:self.cell.chips]]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    def close(self) -> None:
        for ep in self.eps.values():
            for dev_id in list(ep.device_params):
                ep.evict(dev_id)
        del self.server, self.eps
        gc.collect()


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, devs, bytes_limit: Optional[int] = None,
        peak: Optional[Dict[str, float]] = None,
        trace_dir: Optional[str] = None):
    """Run ``cell`` once on ``devs`` (the first ``cell.chips`` of them);
    ``bytes_limit`` and ``peak`` default to the device's own and to
    ``peaks.json``. Returns the result line's object."""
    kind = devs[0].device_kind
    log(f"device_kind: {kind}")
    log(f"device_count: {len(devs)}")
    if peak is None:
        peaks = spec.load_json(os.path.join(spec.BENCH_DIR, "peaks.json"))
        if kind not in peaks:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        peak = peaks[kind]
    conf, traf = cell.config, cell.traffic
    bench = Bench(cell, t_start=t_start, devs=devs, bytes_limit=bytes_limit)
    bench.setup(seed, traced)
    arrivals = tr.schedule(traf, seed, seconds)
    records, served, window, trace = bench.window(arrivals, seconds,
                                                  trace_dir, traced)
    setup_s = window[0] - t_start
    unaccounted = bench.stop()
    log(f"compiles in the window: {bench.counter.compiles} backend "
        f"compiles, {bench.counter.traces} traces (there should be none)")
    failed = sum(1 for r in records if r.failed)
    counts: Dict[str, int] = {}
    for r in records:
        counts[r.start_type] = counts.get(r.start_type, 0) + 1
    log(f"window: {len(arrivals)} arrivals in {seconds} s, start types "
        f"{dict(sorted(counts.items()))}, {failed} failed")
    peak_bytes = bench.memory_peak()
    differs = bench.differs
    req, fam = bench.req, bench.fam
    bench.close()

    t3 = time.monotonic()
    ref = check.Reference(conf, req["batch"], req["prompt"],
                          req["new_tokens"])
    picked = check.sample(served, traf["check"]["sample"], seed,
                          traf["check"].get("functions"))
    gaps = check.widest_gaps(ref, picked)["served"]
    log(f"check: {len(picked)} invocations "
        f"({sum(p.start_type != 'warm' for p in picked)} right after an "
        f"upload) against the reference in {time.monotonic() - t3:.3f} s")

    ctx = Context(records=records, window=window, setup_s=setup_s,
                  request=req, work=fam.work(conf, req["batch"],
                                             req["prompt"],
                                             req["new_tokens"]),
                  peak=peak, trace=trace)
    metrics = {}
    for m in cell.metrics(traced):
        v = m.read(ctx)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    checks = {
        "token_gap": {"value": max(gaps) if gaps else float("inf"),
                      "limit": conf["gap_limit"]},
        "unaccounted": {"value": unaccounted, "limit": 0},
        "config_mismatch": {"value": len(differs), "limit": 0},
    }
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(arrivals), "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        out["breakdown"] = {"device_ops": trace.device_ops(),
                            "idle_gaps": trace.idle_gaps()}
    out["checks"] = checks
    return out


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))
