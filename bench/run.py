#!/usr/bin/env python3
"""On-chip benchmark of the MQFQ-Sticky serving stack.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Serves the cell's functions (full-width models, random weights from fixed
seeds) through ``make_server(executor="wallclock")`` on one process's
chips, drives an open-loop window of ``--seconds`` from ``--seed``, then
checks a sample of what was served against the plain reference. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiled sub-window.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``, each compared number beside its limit; the last lines of
stderr repeat the checks. Exits non-zero, printing no result, where JAX
finds no accelerator or fewer chips than the cell asks for.
"""
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace here instead of "
                         "deleting it")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devs = cell_mod.devices(cell.chips)
    except cell_mod.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START, devs=devs, trace_dir=args.trace_dir)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
