#!/usr/bin/env python
"""obs_report — join trace JSONL + metrics snapshots into a per-request
waterfall and SLO report.

The read side of the observability plane: ``FLAGS_trace_dir`` (or
``tools/serve.py --trace-dir``) streams finished spans as LogWriter
JSONL; ``--metrics`` points at a Prometheus textfile written by
``profiler.metrics.write_textfile`` (or scraped from ``--metrics-port``).
This tool joins them:

    python tools/obs_report.py --trace-dir /tmp/traces
    python tools/obs_report.py --trace-dir /tmp/traces --waterfall 3
    python tools/obs_report.py --trace-dir /tmp/traces \
        --metrics /tmp/metrics.prom --slo-p99-ms 250 --json

Cluster mode (``--cluster``) reads the Router's MERGED trace JSONL
(serving.cluster.obs.ClusterObserver's sink): spans from N processes,
already re-stamped onto the router wall timeline with their origin
under ``process``.  A trace is judged as a CROSS-PROCESS chain — one
``route`` root, per-process subroots joined by trace_id, and for
disaggregated decode the full route→prefill→handoff→decode shape.

Postmortem mode (``--postmortem postmortem_<id>.json``) reads a flight-
recorder artifact (profiler.flight) and reports what the dead process
knew: recent spans, recompile-ledger tail, metric families, dump
reason.

Per trace it checks the span chain is COMPLETE (every phase its request
kind requires) and WELL-NESTED (children inside the root window, in
order); across traces it aggregates per-phase p50/p99 and total-latency
percentiles.  Exit code is non-zero when any chain is incomplete or
mis-nested, or a ``--slo-p99-ms`` bound is violated — the smoke test's
assertion surface.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# phases a complete request chain must carry, by root-span kind.  h2d /
# d2h are pipeline-path extras (the synchronous executor backend fences
# internally and legitimately lacks them).
REQUIRED_PHASES = {
    "dense": {"queue_wait", "pack", "execute", "reply"},
    "decode": {"queue_wait", "pack", "prefill", "decode", "reply"},
}
# a decode request served by the slot loop (FLAGS_decode_slots): its
# phases are cut from the SlotRequest stamps (serving/slots.py) instead
# of the scanned path's prefill/decode
SLOT_DECODE_PHASES = {"queue_wait", "pack", "slot_queue", "slot_prefill",
                      "slot_decode", "reply_hold", "reply"}
# cross-process chains: what a cluster trace must carry beyond the
# route root.  Unified routing proxies the whole request to one replica
# (request subroot + its in-process phases); disaggregated decode
# splits prefill and decode across pools with an explicit handoff.
REQUIRED_CLUSTER_PHASES = {
    "unified": {"dispatch", "request"},
    "disaggregated": {"dispatch", "prefill", "handoff", "decode"},
}
# tolerance for cross-thread monotonic stamping at span edges
_EDGE_EPS_S = 0.005
# cross-process nesting tolerance: clock-skew correction is RTT-midpoint
# accurate, so allow a wider (but still tight) edge epsilon
_CLUSTER_EPS_S = 0.05


def load_traces(trace_dir):
    """Read every trace/span JSONL record under ``trace_dir`` (rotated
    generations included) -> {trace_id: [span dicts, oldest first]}."""
    from paddle_tpu.utils.monitor import LogWriter
    spans = LogWriter.read_events(trace_dir).get("trace/span", [])
    out = {}
    for s in spans:
        out.setdefault(s["trace_id"], []).append(s)
    return out


def required_phases(kind, names):
    """The phases a root of ``kind`` must carry, given the span names it
    has: a decode request that went through slots shows ``slot_queue``."""
    if kind == "decode" and "slot_queue" in names:
        return SLOT_DECODE_PHASES
    return REQUIRED_PHASES.get(kind, set())


def check_chain(spans):
    """Validate one trace: returns (ok, problems list).  Complete =
    every phase the root's kind requires is present; well-nested = every
    child span lies inside the root window (±edge epsilon) and the root
    was finished."""
    problems = []
    roots = [s for s in spans if s.get("parent_id") is None]
    if len(roots) != 1:
        return False, [f"expected exactly one root span, got {len(roots)}"]
    root = roots[0]
    kind = root.get("attrs", {}).get("kind", "dense")
    names = {s["name"] for s in spans if s is not root}
    missing = required_phases(kind, names) - names
    if missing:
        problems.append(f"incomplete chain (kind={kind}): missing "
                        f"{sorted(missing)}")
    r0 = root["t0"]
    r1 = root["t0"] + root["dur_ms"] / 1e3
    for s in spans:
        if s is root:
            continue
        s0, s1 = s["t0"], s["t0"] + s["dur_ms"] / 1e3
        if s0 < r0 - _EDGE_EPS_S or s1 > r1 + _EDGE_EPS_S:
            problems.append(
                f"span {s['name']!r} [{s0:.6f}, {s1:.6f}] escapes the "
                f"root window [{r0:.6f}, {r1:.6f}]")
    return not problems, problems


def _root_span(spans):
    """The trace's display root: the ``route`` span when present (a
    cluster trace has per-process subroots too), else the first
    parentless span."""
    roots = [s for s in spans if s.get("parent_id") is None]
    for s in roots:
        if s["name"] == "route":
            return s
    return roots[0]


def check_cluster_chain(spans, eps=_CLUSTER_EPS_S):
    """Validate one CROSS-PROCESS trace assembled by the Router:

    * exactly one ``route`` root (the router's);
    * other parentless spans are per-process subroots joined by
      trace_id — legal, but every span must still lie inside the route
      window after clock-skew correction (±``eps``);
    * complete = the unified shape (dispatch + the replica's request
      chain) or, when a ``handoff`` span is present, the disaggregated
      route→prefill→handoff→decode shape."""
    problems = []
    routes = [s for s in spans
              if s.get("parent_id") is None and s["name"] == "route"]
    if len(routes) != 1:
        return False, [f"expected exactly one route root, "
                       f"got {len(routes)}"]
    root = routes[0]
    kind = root.get("attrs", {}).get("kind", "dense")
    names = {s["name"] for s in spans if s is not root}
    shape = "disaggregated" if "handoff" in names else "unified"
    required = set(REQUIRED_CLUSTER_PHASES[shape])
    if shape == "unified":
        required |= required_phases(kind, names)
    missing = required - names
    if missing:
        problems.append(f"incomplete cluster chain (kind={kind}, "
                        f"{shape}): missing {sorted(missing)}")
    r0 = root["t0"]
    r1 = root["t0"] + root["dur_ms"] / 1e3
    for s in spans:
        if s is root:
            continue
        s0, s1 = s["t0"], s["t0"] + s["dur_ms"] / 1e3
        if s0 < r0 - eps or s1 > r1 + eps:
            problems.append(
                f"span {s['name']!r} "
                f"(process {s.get('process', '?')}) "
                f"[{s0:.6f}, {s1:.6f}] escapes the route window "
                f"[{r0:.6f}, {r1:.6f}] after skew correction")
    return not problems, problems


def waterfall(spans, width=48):
    """Text waterfall for one trace: spans as offset bars under the
    root, phase order preserved."""
    root = _root_span(spans)
    total = max(root["dur_ms"], 1e-6)
    lines = [f"trace {root['trace_id']}  {root['name']} "
             f"{root['dur_ms']:.2f} ms  {root.get('attrs', {})}"]
    for s in sorted((s for s in spans if s is not root),
                    key=lambda s: s["t0"]):
        off_ms = (s["t0"] - root["t0"]) * 1e3
        a = int(max(0.0, off_ms) / total * width)
        b = max(a + 1, int((max(0.0, off_ms) + s["dur_ms"]) / total
                           * width))
        bar = " " * a + "#" * min(b - a, width - a)
        n_compiles = sum(1 for e in s.get("events", [])
                         if e.get("name") == "compile")
        extra = f"  [{n_compiles} COMPILE]" if n_compiles else ""
        nm = s["name"]
        if s.get("process"):
            nm = f"{nm}@{s['process']}"
        lines.append(f"  {nm:<12} {off_ms:>9.2f} ms "
                     f"+{s['dur_ms']:>9.2f} ms |{bar:<{width}}|{extra}")
    return "\n".join(lines)


def _pctl(sorted_vals, p):
    if not sorted_vals:
        return None
    rank = max(0, min(len(sorted_vals) - 1,
                      int(round(p / 100.0 * len(sorted_vals) + 0.5)) - 1))
    return sorted_vals[rank]


def parse_prometheus_text(text):
    """Minimal (and strict) Prometheus 0.0.4 text parser -> {metric:
    {labels-string: float}}.  Raises ValueError on a malformed line —
    the smoke test runs it over a live scrape as the format gate."""
    import re
    sample = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
        r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\",?)*)\})?"
        r" ([0-9.eE+-]+|NaN|[+-]Inf)$")
    out = {}
    for i, line in enumerate(text.splitlines()):
        if not line.strip():
            continue
        if line.startswith("#"):
            if not (line.startswith("# HELP ")
                    or line.startswith("# TYPE ")):
                raise ValueError(f"line {i + 1}: bad comment {line!r}")
            continue
        m = sample.match(line)
        if m is None:
            raise ValueError(f"line {i + 1}: bad sample {line!r}")
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        out.setdefault(name, {})[labels] = float(value)
    return out


def build_report(traces, slo_p99_ms=None, metrics_path=None,
                 cluster=False):
    """Aggregate check + percentile report over every trace.  With
    ``cluster`` the judge is :func:`check_cluster_chain` and the report
    additionally counts distinct processes and chain shapes."""
    per_phase = {}
    totals = []
    bad = {}
    kinds = {}
    shapes = {}
    max_procs = 0
    checker = check_cluster_chain if cluster else check_chain
    for tid, spans in sorted(traces.items()):
        ok, problems = checker(spans)
        if not ok:
            bad[tid] = problems
            continue
        root = _root_span(spans)
        totals.append(root["dur_ms"])
        kinds[root.get("attrs", {}).get("kind", "dense")] = \
            kinds.get(root.get("attrs", {}).get("kind", "dense"), 0) + 1
        if cluster:
            names = {s["name"] for s in spans}
            shape = "disaggregated" if "handoff" in names else "unified"
            shapes[shape] = shapes.get(shape, 0) + 1
            max_procs = max(max_procs, len(
                {s.get("process") for s in spans if s.get("process")}))
        for s in spans:
            if s is not root:
                per_phase.setdefault(s["name"], []).append(s["dur_ms"])
    totals.sort()
    report = {
        "traces": len(traces),
        "complete": len(totals),
        "incomplete": {k: v for k, v in sorted(bad.items())[:8]},
        "kinds": kinds,
        "total_ms": {"p50": _pctl(totals, 50), "p99": _pctl(totals, 99),
                     "max": totals[-1] if totals else None},
        "phases_ms": {
            name: {"count": len(vs), "p50": _pctl(sorted(vs), 50),
                   "p99": _pctl(sorted(vs), 99)}
            for name, vs in sorted(per_phase.items())},
    }
    if cluster:
        report["shapes"] = shapes
        report["max_processes"] = max_procs
    if slo_p99_ms is not None and totals:
        report["slo_p99_ms"] = slo_p99_ms
        report["slo_met"] = report["total_ms"]["p99"] <= slo_p99_ms
    if metrics_path:
        with open(metrics_path) as f:
            fams = parse_prometheus_text(f.read())
        report["metrics"] = {
            name: fams[name] for name in sorted(fams)
            if name.split("_bucket")[0].startswith(
                ("serving_", "train_step_", "wide_deep_", "cluster_",
                 "router_"))}
    rc = 1 if bad else 0
    if report.get("slo_met") is False:
        rc = 1
    return report, rc


def postmortem_report(path):
    """Read + validate a flight-recorder artifact -> (report, rc)."""
    with open(path) as f:
        rec = json.load(f)
    problems = []
    if not str(rec.get("schema", "")).startswith(
            "paddle_tpu/flight-recorder/"):
        problems.append(f"unrecognized schema {rec.get('schema')!r}")
    for key in ("reason", "wall", "spans", "ledger", "metrics"):
        if key not in rec:
            problems.append(f"missing key {key!r}")
    spans = rec.get("spans") or []
    report = {
        "path": path,
        "schema": rec.get("schema"),
        "id": rec.get("id"),
        "pid": rec.get("pid"),
        "reason": rec.get("reason"),
        "age_s": round(time.time() - float(rec["wall"]), 3)
        if "wall" in rec else None,
        "dumps": rec.get("dumps"),
        "trace_mode": rec.get("trace_mode"),
        "spans": len(spans),
        "ledger_events": len(rec.get("ledger") or []),
        "metric_families": len((rec.get("metrics") or {})
                               .get("families") or []),
        "last_spans": [{"name": s.get("name"),
                        "trace_id": s.get("trace_id"),
                        "dur_ms": s.get("dur_ms")}
                       for s in spans[-5:]],
        "problems": problems,
    }
    return report, 1 if problems else 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="obs_report",
        description="join trace JSONL + metrics snapshots into "
                    "per-request waterfalls and an SLO report")
    ap.add_argument("--trace-dir", default=None,
                    help="directory of LogWriter trace JSONL "
                         "(FLAGS_trace_dir / serve.py --trace-dir)")
    ap.add_argument("--cluster", action="store_true",
                    help="judge traces as cross-process chains (the "
                         "Router's merged trace sink: route root + "
                         "per-process subroots, disaggregated "
                         "prefill/handoff/decode shapes)")
    ap.add_argument("--postmortem", default=None, metavar="PATH",
                    help="read one flight-recorder artifact "
                         "(postmortem_<id>.json) instead of a trace "
                         "dir; rc!=0 when unreadable/malformed")
    ap.add_argument("--metrics", default=None,
                    help="Prometheus textfile to validate + embed "
                         "(profiler.metrics.write_textfile output)")
    ap.add_argument("--slo-p99-ms", type=float, default=None,
                    help="fail (rc!=0) when total p99 exceeds this")
    ap.add_argument("--waterfall", type=int, default=0, metavar="N",
                    help="print text waterfalls of the N slowest "
                         "complete requests")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if args.postmortem:
        report, rc = postmortem_report(args.postmortem)
        if args.as_json:
            print(json.dumps(report, indent=1))
        else:
            print(f"postmortem {report['path']}: id={report['id']} "
                  f"pid={report['pid']} reason={report['reason']!r} "
                  f"age={report['age_s']}s")
            print(f"  {report['spans']} spans, "
                  f"{report['ledger_events']} ledger events, "
                  f"{report['metric_families']} metric families, "
                  f"{report['dumps']} prior dumps, "
                  f"trace={report['trace_mode']}")
            for s in report["last_spans"]:
                print(f"  span {s['name']:<12} {s['dur_ms']:>9.3f} ms "
                      f"trace {s['trace_id']}")
            for p in report["problems"]:
                print(f"  PROBLEM: {p}")
        return rc

    if not args.trace_dir:
        ap.error("--trace-dir is required (or use --postmortem)")

    traces = load_traces(args.trace_dir)
    report, rc = build_report(traces, slo_p99_ms=args.slo_p99_ms,
                              metrics_path=args.metrics,
                              cluster=args.cluster)
    if args.as_json:
        print(json.dumps(report, indent=1))
    else:
        print(f"obs_report: {report['complete']}/{report['traces']} "
              f"complete span chains  kinds={report['kinds']}"
              + (f"  shapes={report['shapes']}  "
                 f"max_processes={report['max_processes']}"
                 if args.cluster else ""))
        t = report["total_ms"]
        if t["p50"] is not None:
            print(f"  total: p50 {t['p50']:.2f} ms  p99 {t['p99']:.2f} ms"
                  f"  max {t['max']:.2f} ms")
        for name, st in report["phases_ms"].items():
            print(f"  {name:<12} n={st['count']:<6} p50 "
                  f"{st['p50']:>9.3f} ms  p99 {st['p99']:>9.3f} ms")
        for tid, problems in report["incomplete"].items():
            print(f"  BAD {tid}: {'; '.join(problems)}")
        if "slo_met" in report:
            print(f"  SLO p99<={report['slo_p99_ms']} ms: "
                  f"{'met' if report['slo_met'] else 'VIOLATED'}")
    if args.waterfall:
        checker = check_cluster_chain if args.cluster else check_chain
        complete = []
        for tid, spans in traces.items():
            ok, _ = checker(spans)
            if ok:
                complete.append((_root_span(spans)["dur_ms"], tid))
        for _, tid in sorted(complete, reverse=True)[:args.waterfall]:
            print()
            print(waterfall(traces[tid]))
    return rc


if __name__ == "__main__":
    sys.exit(main())
