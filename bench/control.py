#!/usr/bin/env python3
"""Readings that set a cell's ``gap_limit``: the served path's widest
token gap on many seeds, and the control's on the same samples.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One set-up, then a short open-loop window at the cell's own rate per
seed, each sampled as a run samples its window; once every window is
served the program's state is freed and the reference runs over all the
samples. The control is that reference one precision step below the
configuration's (fp8 under bfloat16): at each position it reads the
reference gap of the token the control puts first. One JSON line per
seed, then one with the widest served gap and the narrowest control
gap. Not part of a run."""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import cell as cell_mod  # noqa: E402
from harness import check, spec  # noqa: E402
from harness import traffic as tr  # noqa: E402


def readings(cell: spec.Cell, seeds, seconds: float, *, t_start: float,
             devs, bytes_limit=None):
    """{seed: {"served": widest served gap, "control": widest control
    gap}} for each seed's window."""
    bench = cell_mod.Bench(cell, t_start=t_start, devs=devs,
                           bytes_limit=bytes_limit)
    bench.setup(seeds[0], traced=False)
    picked = {}
    for seed in seeds:
        arrivals = tr.schedule(cell.traffic, seed, seconds)
        _, served, _, _ = bench.window(arrivals, seconds)
        how = cell.traffic["check"]
        picked[seed] = check.sample(served, how["sample"], seed,
                                    how.get("functions"))
    bench.stop()
    bench.close()
    req = cell.config["request"]
    ref = check.Reference(cell.config, req["batch"], req["prompt"],
                          req["new_tokens"])
    flat = [(seed, s) for seed in seeds for s in picked[seed]]
    gaps = check.widest_gaps(ref, [s for _, s in flat], control=True)
    # widest_gaps groups by function: map back by identity
    order = sorted(range(len(flat)), key=lambda i: flat[i][1].weight_seed)
    out = {seed: {"served": 0.0, "control": 0.0} for seed in seeds}
    for j, i in enumerate(order):
        seed = flat[i][0]
        if gaps["served"][j] >= out[seed]["served"]:
            out[seed]["first_rank"] = int(gaps["first_rank"][j])
            out[seed]["position"] = int(gaps["position"][j])
            out[seed]["start_type"] = flat[i][1].start_type
        for key in ("served", "control"):
            out[seed][key] = max(out[seed][key], gaps[key][j])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devs = cell_mod.devices(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = readings(cell, seeds, args.seconds, t_start=T_START, devs=devs)
    for seed in seeds:
        print(json.dumps({"workload": cell.name, "seed": seed, **out[seed]}))
    print(json.dumps({
        "workload": cell.name, "device_kind": devs[0].device_kind,
        "served_max": max(v["served"] for v in out.values()),
        "control_min": min(v["control"] for v in out.values())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
