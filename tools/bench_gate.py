#!/usr/bin/env python
"""bench_gate — noise-aware regression gate between two bench rounds.

Compares two ``BENCH_*.json`` artifacts (bench.py ``--json`` rounds) and
fails, metric by metric, only on regressions that clear a per-metric
noise tolerance — a raw ``new < old`` comparison flags every run of a
jittery CPU-backed lane, so the gate has to know what noise looks like:

  * every workload gets a **tolerance band** (default ``--tolerance-pct``,
    overridable per metric with ``--tolerance name=pct``); a drop inside
    the band is ``ok (within noise)``, outside is a ``regression``;
  * rounds self-report their dispatch-floor health
    (``dispatch_floor_ms`` / ``degraded`` / ``floor_ratio``): when either
    round ran **degraded** — the per-step dispatch floor dominates the
    measurement — or the two rounds' floors disagree by more than
    ``--floor-drift-pct``, the workload is tagged ``dispersed`` and its
    tolerance is **widened** (×``--dispersion-widen``) instead of letting
    scheduler noise masquerade as a perf loss;
  * a workload present in the old round but missing from the new one is
    a regression outright (a silently dropped benchmark is the worst
    kind of "improvement").

    python tools/bench_gate.py BENCH_prev.json BENCH_new.json
    python tools/bench_gate.py old.json new.json --tolerance-pct 5 \\
        --tolerance mnist_lenet_static=25 --json

Exit code 0 = no regression outside tolerance; 1 = at least one.
Stdlib-only and importable: tests drive :func:`compare` directly.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Optional, Tuple

DEFAULT_TOLERANCE_PCT = 5.0
DEFAULT_DISPERSION_WIDEN = 3.0
DEFAULT_FLOOR_DRIFT_PCT = 20.0


def _workloads(round_: dict) -> Dict[str, dict]:
    parsed = round_.get("parsed") or {}
    wl = dict(parsed.get("workloads") or {})
    if not wl and parsed.get("metric"):
        # degenerate round: only the headline metric was parsed
        wl[parsed["metric"]] = {"value": parsed.get("value"),
                                "unit": parsed.get("unit")}
    return wl


def _round_dispersed(round_: dict) -> Tuple[bool, Optional[float]]:
    parsed = round_.get("parsed") or {}
    return bool(parsed.get("degraded")), parsed.get("dispatch_floor_ms")


def compare(old: dict, new: dict,
            default_tol_pct: float = DEFAULT_TOLERANCE_PCT,
            per_metric: Optional[Dict[str, float]] = None,
            dispersion_widen: float = DEFAULT_DISPERSION_WIDEN,
            floor_drift_pct: float = DEFAULT_FLOOR_DRIFT_PCT,
            ) -> Tuple[dict, int]:
    """Gate ``new`` against ``old``: returns ``(report, rc)``.

    All metrics are throughputs (bigger is better).  ``per_metric`` maps
    workload name -> tolerance pct, overriding ``default_tol_pct``.
    """
    per_metric = per_metric or {}
    old_wl, new_wl = _workloads(old), _workloads(new)
    old_deg, old_floor = _round_dispersed(old)
    new_deg, new_floor = _round_dispersed(new)
    floor_drift = None
    if old_floor and new_floor:
        floor_drift = abs(new_floor - old_floor) / old_floor * 100.0
    rounds_dispersed = (old_deg or new_deg
                        or (floor_drift is not None
                            and floor_drift > floor_drift_pct))
    report = {
        "old": {"n": old.get("n"), "degraded": old_deg,
                "dispatch_floor_ms": old_floor},
        "new": {"n": new.get("n"), "degraded": new_deg,
                "dispatch_floor_ms": new_floor},
        "floor_drift_pct": (round(floor_drift, 2)
                            if floor_drift is not None else None),
        "dispersed": rounds_dispersed,
        "default_tolerance_pct": float(default_tol_pct),
        "dispersion_widen": float(dispersion_widen),
        "metrics": {},
    }
    rc = 0
    for name in sorted(set(old_wl) | set(new_wl)):
        o, n = old_wl.get(name), new_wl.get(name)
        tol = float(per_metric.get(name, default_tol_pct))
        row = {"tolerance_pct": tol, "dispersed": rounds_dispersed}
        if o is None:
            row.update(verdict="new", new=n.get("value"),
                       unit=n.get("unit"))
            report["metrics"][name] = row
            continue
        if n is None or n.get("value") is None:
            row.update(verdict="missing", old=o.get("value"),
                       unit=o.get("unit"))
            report["metrics"][name] = row
            rc = 1
            continue
        ov, nv = float(o["value"]), float(n["value"])
        if rounds_dispersed:
            tol *= float(dispersion_widen)
            row["tolerance_pct"] = tol
        delta_pct = (nv - ov) / ov * 100.0 if ov else 0.0
        row.update(old=ov, new=nv, unit=n.get("unit", o.get("unit")),
                   delta_pct=round(delta_pct, 3))
        if delta_pct < -tol:
            row["verdict"] = "regression"
            rc = 1
        elif delta_pct > tol:
            row["verdict"] = "improved"
        else:
            row["verdict"] = "ok"
        report["metrics"][name] = row
    report["rc"] = rc
    return report, rc


def _parse_overrides(pairs) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for p in pairs or []:
        name, _, pct = p.partition("=")
        if not name or not pct:
            raise SystemExit(f"--tolerance wants name=pct, got {p!r}")
        out[name] = float(pct)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_gate",
        description="noise-aware regression gate between two bench.py "
                    "--json rounds (per-metric tolerance, dispersion "
                    "tagging, rc gate)")
    ap.add_argument("old", help="baseline round (BENCH_*.json)")
    ap.add_argument("new", help="candidate round (BENCH_*.json)")
    ap.add_argument("--tolerance-pct", type=float,
                    default=DEFAULT_TOLERANCE_PCT,
                    help="default per-metric noise band, percent "
                         "(default %(default)s)")
    ap.add_argument("--tolerance", action="append", metavar="NAME=PCT",
                    help="per-metric tolerance override (repeatable)")
    ap.add_argument("--dispersion-widen", type=float,
                    default=DEFAULT_DISPERSION_WIDEN,
                    help="tolerance multiplier when a round is degraded "
                         "or the dispatch floors drifted "
                         "(default %(default)s)")
    ap.add_argument("--floor-drift-pct", type=float,
                    default=DEFAULT_FLOOR_DRIFT_PCT,
                    help="dispatch_floor_ms disagreement between rounds "
                         "that flags dispersion (default %(default)s)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the full report as JSON")
    args = ap.parse_args(argv)

    with open(args.old) as f:
        old = json.load(f)
    with open(args.new) as f:
        new = json.load(f)
    report, rc = compare(
        old, new, default_tol_pct=args.tolerance_pct,
        per_metric=_parse_overrides(args.tolerance),
        dispersion_widen=args.dispersion_widen,
        floor_drift_pct=args.floor_drift_pct)
    if args.as_json:
        print(json.dumps(report, indent=1))
        return rc
    for name, row in report["metrics"].items():
        v = row["verdict"]
        if v == "new":
            print(f"{name:>24}: NEW {row['new']} {row.get('unit', '')}")
            continue
        if v == "missing":
            print(f"{name:>24}: MISSING from new round (regression)")
            continue
        tag = " [dispersed]" if row["dispersed"] else ""
        print(f"{name:>24}: {row['old']:>12.1f} -> {row['new']:>12.1f} "
              f"{row.get('unit') or '':<10} {row['delta_pct']:>+8.2f}% "
              f"(tol ±{row['tolerance_pct']:.1f}%) {v.upper()}{tag}")
    print(f"bench_gate: rc={rc}"
          + (" (dispersed rounds — tolerance widened)"
             if report["dispersed"] else ""))
    return rc


if __name__ == "__main__":
    sys.exit(main())
