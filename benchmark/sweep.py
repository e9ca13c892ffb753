#!/usr/bin/env python3
"""The knee sweep of an open-loop cell: one process, one set-up, then the
cell's own traffic at each of several fixed rates for ``--seconds`` each.
Not part of a run; made once when the cell is defined (PERF.md section 4),
and again by a later benchmark PR when an optimisation has moved the knee.

    python3 benchmark/sweep.py --workload <open-loop cell> --rates 2,3,4,5 \
        --seconds 40 --repeats 2 --seed 1 --out chiprun_out/sweep.json

The knee is the highest rate at which at least 98% of the due requests
resolve and the backlog (requests sent and unresolved) at the window's end
is no larger than at its middle.  The cell's rate is 0.8 x the knee.  The
traffic file's arrivals are one fixed sequence of gaps times 1/rate, so
the realised rates rise with the nominal ones; repeat ``k`` replays the
sweep on another sequence from the same distributions (``shape_seed`` +
k), which says how far the knee is a property of the one trace.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def backlog(records, t):
    return sum(1 for r in records if r.sent is not None and r.sent <= t
               and (r.done is None or r.done > t))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmark import harness, run as bench_run
    from benchmark.runners import serve_slots
    cell = bench_run.load_cell(args.workload)
    bench_run.require_chips(cell["chips"])
    bench_run.enable_compile_cache()
    cfg = cell["config"]
    gen = importlib.import_module(
        f"benchmark.generators.{cell['traffic']['kind']}")
    srv, loop, submit, _ = serve_slots.boot(cfg, args.seed)
    rows = []
    rates = [float(x) for x in args.rates.split(",")]
    shape_seed = int(cell["traffic"]["shape_seed"])
    for rep, rate in ((r, x) for r in range(args.repeats) for x in rates):
        traffic = dict(cell["traffic"], rate_per_s=rate,
                       shape_seed=shape_seed + rep)
        mark = {}
        records, t_open, t_close = gen.drive(
            traffic, args.seed, args.seconds, submit,
            vocab_size=cfg["vocab_size"], slots=int(cfg["serve"]["slots"]),
            on_open=loop.reset_stats,
            on_close=lambda: mark.update(stats=loop.stats()),
            span=harness.span)
        due = [r for r in records if t_open <= r.due < t_close]
        ok = [r for r in due if r.done is not None and r.error is None]
        lat = [1e3 * (r.done - r.due) for r in ok]
        st = mark["stats"]
        mid, end = (backlog(records, (t_open + t_close) / 2),
                    backlog(records, t_close))
        rows.append({
            "repeat": rep, "shape_seed": shape_seed + rep,
            "rate_per_s": rate, "realised_per_s": len(due) / args.seconds,
            "due": len(due), "resolved": len(ok),
            "resolved_share": len(ok) / max(1, len(due)),
            "backlog_mid": mid, "backlog_end": end,
            "below_knee": len(ok) >= 0.98 * len(due) and end <= mid,
            "p50_ms": harness.percentile(lat, 50) if lat else None,
            "p90_ms": harness.percentile(lat, 90) if lat else None,
            "late_p95_ms": harness.percentile(
                [1e3 * (r.sent - r.due) for r in due], 95) if due else None,
            "occupancy_pct": 100.0 * st["emitted_tokens"]
            / max(1, st["steps"] * st["slots"]),
            "steps": st["steps"], "chunks": st["chunks"],
            "session_resets": st["session_resets"]})
        print(json.dumps(rows[-1]), flush=True)
        # let the server empty before the next rate
        t_wait = time.monotonic() + 60
        while time.monotonic() < t_wait and any(
                r.done is None for r in records):
            time.sleep(0.1)
    serve_slots._stop(srv)
    knees = {rep: max((r["rate_per_s"] for r in rows
                       if r["repeat"] == rep and r["below_knee"]), default=None)
             for rep in range(args.repeats)}
    print("knee by repeat: " + json.dumps(knees), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "seed": args.seed, "knee_by_repeat": knees,
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
