"""Cases of the readers of invocation records and of the device trace:
ten synthetic records and a synthetic trace."""
import contextlib

from harness.context import Context, Record
from harness.tracing import Trace

WORK = {"prefill_flops": 4e9, "prefill_bytes": 2e9,
        "token_flops": 1e9, "token_bytes": 5e8}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}
REQ = {"batch": 2, "prompt": 8, "new_tokens": 4}


def rec(fn, due, late, lat, queue, over, ex, start="warm", failed=False):
    return Record(fn=fn, due=due, release=due + late,
                  completion=due + lat, queue_time=queue, overhead=over,
                  service_time=ex, start_type=start, failed=failed)


def records():
    # 10 invocations, latency 0.1 .. 1.0 s; function 1 gets the slow half
    return [rec(i // 5, 100.0 + i, 0.001 * i, 0.1 * (i + 1), 0.01 * i,
                0.002 * i, 0.05 + 0.01 * i,
                "host_warm" if i in (3, 7) else "warm") for i in range(10)]


def trace():
    # one chip: prefill 0-10 ms, decode 10-50 ms, idle, decode 60-80 ms;
    # two execute spans: 0-55 ms and 58-85 ms; window 100 ms
    mods = [("jit__prefill(1)", 0.000, 0.010), ("jit__decode(2)", 0.010, 0.050),
            ("jit__decode(2)", 0.060, 0.080)]
    spans = {"execute": [(0.0, 0.055), (0.058, 0.085)], "upload": [(0.085, 0.1)]}
    return Trace(0.1, {"/device:TPU:0": mods}, spans)


def ctx(**kw):
    base = dict(records=records(), window=(100.0, 110.0), setup_s=42.5,
                request=REQ, work=WORK, peak=PEAK, trace=trace())
    base.update(kw)
    return Context(**base)


@contextlib.contextmanager
def context(traced=True):
    yield ctx() if traced else ctx(trace=None)


# median of 10 by nearest rank is the 5th: 0.5 s; p95 the 10th
CASES = {
    "latency_p50_ms": 500.0,
    "latency_p95_ms": 1000.0,
    # completions at 100.1, 101.2, ..., 110.0: all inside [100, 110]
    "throughput_inv_s": 1.0,
    "setup_s": 42.5,
    "feeder_late_p99_ms": 9.0,
    "queue_wait_p95_ms": 90.0,
    # function 0: latencies 0.1-0.5 (median 0.3), function 1: 0.6-1.0
    # (median 0.8); overall median 0.5
    "fn_latency_ratio": 0.8 / 0.5,
    "upload_start_share": 20.0,
    "overhead_p95_ms": 18.0,
    "execute_ms.lat": 90.0,
    "execute_ms.tput": 90.0,
    # two prefills of max(4e9/1e12, 2e9/1e11) = 20 ms over 10 ms
    "prefill_roofline.lat": 400.0,
    "prefill_roofline.tput": 400.0,
    # 2 x 2 x 4 tokens of max(1 ms, 5 ms) = 80 ms over 60 ms of decode
    "decode_roofline.lat": 100.0 * 0.080 / 0.060,
    "decode_roofline.tput": 100.0 * 0.080 / 0.060,
    # 2 invocations x (4e9 + 8 x 1e9) over 0.1 s x 1e12
    "mfu.lat": 100.0 * 2 * 12e9 / 1e11,
    "mfu.tput": 100.0 * 2 * 12e9 / 1e11,
    "device_idle_share": 30.0,
    "device_idle_share.tput": 30.0,
}
