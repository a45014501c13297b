#!/usr/bin/env python3
"""Find a cell's knee on the chip: one set-up, then an open-loop window
at each of several total rates, in one process.

    python bench/sweep.py --workload <cell> --rates 2,4,6 --seconds 20 --seed 7

Per rate, one JSON line: offered and completed invocations per second
inside the window, the backlog left at its close, and latency p50/p95
over the whole window and over its last quarter (a growing queue shows
as a later quarter slower than the whole). The knee is the highest rate
whose backlog stays near zero and whose last quarter is no slower than
the whole. Not part of a run: the rate it finds goes into the traffic
file."""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cell as cell_mod  # noqa: E402
from harness import spec  # noqa: E402
from harness import traffic as tr  # noqa: E402
from harness.stats import quantile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devs = cell_mod.devices(cell.chips)
    bench = cell_mod.Bench(cell, t_start=T_START, devs=devs)
    bench.setup(args.seed, traced=False)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traf = dict(cell.traffic, rate=dict(cell.traffic["rate"], inv_s=rate))
        arrivals = tr.schedule(traf, args.seed + i, args.seconds)
        recs, _, (lo, hi), _ = bench.window(arrivals, args.seconds)
        done = [r for r in recs if r.completion is not None and not r.failed]
        in_win = sum(1 for r in done if r.completion <= hi)
        late = [r.latency for r in done if r.due >= lo + 0.75 * args.seconds]
        lat = [r.latency for r in done]
        starts = {}
        for r in recs:
            starts[r.start_type] = starts.get(r.start_type, 0) + 1
        print(json.dumps({
            "workload": cell.name, "rate": rate, "offered": len(arrivals)
            / args.seconds, "completed_in_window": in_win / args.seconds,
            "backlog_at_close": len(arrivals) - in_win,
            "p50_ms": 1e3 * quantile(lat, 0.5),
            "p95_ms": 1e3 * quantile(lat, 0.95),
            "last_quarter_p50_ms": 1e3 * (quantile(late, 0.5) or 0),
            "execute_p50_ms": 1e3 * quantile([r.service_time for r in done],
                                             0.5),
            "start_types": starts}), flush=True)
    bench.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
