#!/usr/bin/env python3
"""The controls: what the comparison that decides ``correct`` reads when
the reference, computed in the nearest precision below the configuration's,
stands in the program's place.  Run by hand on the chip at the cell's own
size when a limit is set or changed (PERF.md section 2 gives the readings);
a run of the benchmark never runs it.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from benchmark import run as bench_run
    cell = bench_run.load_cell(args.workload)
    bench_run.require_chips(cell["chips"])
    bench_run.enable_compile_cache()
    runner = importlib.import_module(
        "benchmark.runners." + cell["config"]["runner"])
    rows = runner.control(cell, [int(s) for s in args.seeds.split(",")],
                          args.seconds)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
