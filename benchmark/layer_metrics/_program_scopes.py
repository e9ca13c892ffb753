"""Device time by program and by named scope: every op of the traced slice
laid under the program run that contains it (``XLA Modules`` line, same
plane, same clock) and under the part of the model that asked for it.

The program names its work with ``jax.named_scope`` and hands out, per
compiled program, ``{instruction name: scope path}``
(``paddle_tpu.profiler.ledger.program_scopes()``, read off the
executables' own text); the capture names each op event by that same
instruction name (``%fusion.555 = ...``).  The two are joined here.  An op
counts with its SELF time (its interval less what runs nested in it: a
``while`` holds its body's ops), so the rows of one program partition its
busy time.  A scope path goes to the bucket of its innermost component
that ``benchmark/scope_buckets.json`` lists; no path or no listed
component is ``unscoped``; an instruction name the program's table does not
hold is ``unknown_instruction`` (the table is of another executable than
the one that ran: it must read 0).  The slice's edge cuts the device's
first and last run: such a run has a shortened event on the ``XLA Modules``
line, or none; it is told by its first (last) op not being the one its
program's runs begin (end) with, and its ops, like those under no run, are
reported apart and enter no metric.

Every function returns None where the program has no such table (a
checkout without ``program_scopes``) or the run has no capture.

    python3 -m benchmark.layer_metrics._program_scopes <trace dir>

prints the table of a capture made by ``paddle_tpu.profiler.Profiler``,
which writes ``program_scopes.json`` beside it.
"""
from __future__ import annotations

import json
import os

from benchmark import trace_reduce

BUCKETS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scope_buckets.json")
UNSCOPED, UNKNOWN = "unscoped", "unknown_instruction"
TOP_OPS = 10


def load_buckets(path: str = BUCKETS_FILE) -> dict:
    """{scope component: bucket} of the data file's {bucket: [components]}."""
    with open(path) as f:
        return {c: b for b, comps in json.load(f).items() for c in comps}


def bucket_of(scope: str, component_bucket: dict) -> str:
    """The bucket of the innermost component of ``scope`` that is listed."""
    for comp in reversed(scope.split("/")):
        if comp in component_bucket:
            return component_bucket[comp]
    return UNSCOPED


def count_buckets(table: dict, component_bucket: dict) -> dict:
    """{bucket: instructions} of one program's scope table: the coverage
    that can be read without a capture (``tools/kv_layout_check.py``)."""
    out = {}
    for entry in table.values():
        b = bucket_of(entry["scope"], component_bucket)
        out[b] = out.get(b, 0) + 1
    return out


def instruction_name(event_name: str) -> str:
    """``%fusion.555 = f32[..] fusion(..)`` -> ``fusion.555``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _events(line):
    """[(start ns, -end ns, name)] of one line.  The end is negated so that
    the tuples' own order is (start, longest first): an op sorts before
    what runs nested in it, with no key function over a million events."""
    return [((a := ev.start_ns), -(a + ev.duration_ns), ev.name)
            for ev in line.events]


def _device_lines(profile):
    """[(module events, op events)] per device plane, sorted ``_events``."""
    out = []
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: _events(line) for line in plane.lines
                 if line.name in (trace_reduce.OPS_LINE,
                                  trace_reduce.MODULES_LINE)}
        ops = lines.get(trace_reduce.OPS_LINE, [])
        if ops:
            out.append((sorted(lines.get(trace_reduce.MODULES_LINE, [])),
                        sorted(ops)))
    return out


def lay(modules, ops):
    """Each op under the run that contains it, with its SELF time: returns
    ``(runs, stray)``, ``runs[k]`` = ``{"ops": {event name: self ns},
    "first": name, "last": name}`` for ``modules[k]`` and ``stray`` the
    same dict of the ops under no run.  Every instant some op runs goes to
    the op that started last among those running, so the self times add
    up to the union of the ops' intervals however they nest or overlap
    (a ``while`` holds its body's ops).  One pass; both lists sorted."""
    runs = [{"ops": {}, "first": None, "last": None} for _ in modules]
    stray = {}
    stack, t, k, n_mod = [], 0.0, 0, len(modules)
    run = None
    for a, nb, name in ops:
        while stack and stack[-1][0] <= a:      # what ended before this op
            e, n, into = stack.pop()
            if e > t:
                into[n] = into.get(n, 0.0) + e - t
                t = e
        if stack:                               # nested: its parent's run
            if a > t:
                _, n, into = stack[-1]
                into[n] = into.get(n, 0.0) + a - t
                t = a
        else:
            if a > t:
                t = a
            while k < n_mod and -modules[k][1] <= a:
                k += 1
            run = runs[k] if k < n_mod and modules[k][0] <= a \
                and nb >= modules[k][1] else None
            if run is not None and run["first"] is None:
                run["first"] = name
        if run is not None:
            run["last"] = name
        stack.append((-nb, name, stray if run is None else run["ops"]))
    while stack:
        e, n, into = stack.pop()
        if e > t:
            into[n] = into.get(n, 0.0) + e - t
            t = e
    return runs, stray


def _cut_runs(modules, runs) -> set:
    """Which of a device's first and last run the slice's edge cut: the
    one without ops, or whose first (last) op is not the one that most
    runs of its program begin (end) with.  A program with fewer than three
    runs has no majority: its runs are taken as whole."""
    cut = set()
    for k, side in ((0, "first"), (len(modules) - 1, "last")) \
            if modules else ():
        seen = [r[side] for m, r in zip(modules, runs)
                if m[2] == modules[k][2] and r["ops"]]
        if not runs[k]["ops"]:
            cut.add(k)
        elif len(seen) >= 3 and runs[k][side] != max(set(seen),
                                                     key=seen.count):
            cut.add(k)
    return cut


def reduce_profile(profile, scopes: dict, component_bucket: dict) -> dict:
    """The table of one capture: ``{"programs": {program: {...}},
    "busy_s", "scoped_s", "cut_s", "no_module_s"}`` (see ``_finish`` for a
    program's fields).  ``scopes`` is ``program_scopes()``'s table."""
    by_program = {}
    cut = stray_s = 0.0
    for modules, ops in _device_lines(profile):
        runs, stray = lay(modules, ops)
        stray_s += sum(stray.values()) / 1e9
        edge = _cut_runs(modules, runs)
        for k, ((a, nb, name), run) in enumerate(zip(modules, runs)):
            if k in edge:
                cut += sum(run["ops"].values()) / 1e9
                continue
            p = by_program.setdefault(
                trace_reduce._FINGERPRINT.sub("", name),
                {"module_s": [], "by_op": {}})
            p["module_s"].append((-nb - a) / 1e9)
            by_op = p["by_op"]
            for op, ns in run["ops"].items():
                by_op[op] = by_op.get(op, 0.0) + ns / 1e9
    programs, busy, scoped = {}, 0.0, 0.0
    for prog, p in by_program.items():
        table = scopes.get(prog)
        p["by_bucket"], p["backward"] = {}, {}
        for op, s in p["by_op"].items():
            entry = None if table is None \
                else table.get(instruction_name(op))
            if table is None:
                bucket, scope = UNSCOPED, ""
            elif entry is None:
                bucket, scope = UNKNOWN, ""
            else:
                scope = entry["scope"]
                bucket = bucket_of(scope, component_bucket)
                if entry.get("backward"):
                    p["backward"][bucket] = p["backward"].get(bucket, 0.0) + s
            p["by_bucket"][bucket] = p["by_bucket"].get(bucket, 0.0) + s
            p["by_op"][op] = (s, scope, bucket)
        busy += sum(p["by_bucket"].values())
        scoped += sum(s for b, s in p["by_bucket"].items()
                      if b not in (UNSCOPED, UNKNOWN))
        programs[prog] = _finish(p, table is not None)
    return {"programs": programs, "busy_s": busy, "scoped_s": scoped,
            "cut_s": cut, "no_module_s": stray_s}


def _finish(p: dict, has_table: bool) -> dict:
    """One program's row: its ``runs``, the ``mean_ms`` (``min_ms``,
    ``max_ms``) of a run on the ``XLA Modules`` line, ``ops_ms`` the ops'
    self time a run (less than ``mean_ms`` by the gaps between ops),
    ``buckets`` {bucket: mean ms a run}, ``unscoped`` and
    ``unknown_instruction`` ms apart, ``backward`` the part of each bucket
    under a ``transpose(..)``, and the heaviest ops by self time with
    their scope: of the program, and of its residue (no bucket)."""
    n = len(p["module_s"])
    ms = lambda s: 1e3 * s / n                                # noqa: E731
    by = p["by_bucket"]
    ranked = sorted(p["by_op"].items(), key=lambda x: -x[1][0])

    def top(keep):
        return [[trace_reduce.short_name(name), ms(s), scope]
                for name, (s, scope, bucket) in ranked if keep(bucket)
                ][:TOP_OPS]
    return {
        "runs": n, "has_table": has_table,
        "mean_ms": ms(sum(p["module_s"])), "min_ms": 1e3 * min(p["module_s"]),
        "max_ms": 1e3 * max(p["module_s"]), "ops_ms": ms(sum(by.values())),
        "buckets": {b: ms(s) for b, s in sorted(by.items())
                    if b not in (UNSCOPED, UNKNOWN)},
        UNSCOPED: ms(by.get(UNSCOPED, 0.0)),
        UNKNOWN: ms(by.get(UNKNOWN, 0.0)),
        "backward": {b: ms(s) for b, s in sorted(p["backward"].items())},
        "top_ops": top(lambda bucket: True),
        "top_residue": top(lambda bucket: bucket in (UNSCOPED, UNKNOWN)),
    }


def report(table: dict) -> None:
    """One line per program and its heaviest ops (PERF.md section 5 is
    written from these lines)."""
    r3 = lambda d: {k: round(v, 3) for k, v in d.items()}     # noqa: E731
    for prog, p in sorted(table["programs"].items(),
                          key=lambda x: -x[1]["ops_ms"] * x[1]["runs"]):
        print(f"device seconds by scope, {prog}: " + json.dumps({
            "runs": p["runs"], "mean_ms": round(p["mean_ms"], 3),
            "min_ms": round(p["min_ms"], 3), "max_ms": round(p["max_ms"], 3),
            "ops_ms": round(p["ops_ms"], 3), "buckets": r3(p["buckets"]),
            UNSCOPED: round(p[UNSCOPED], 3), UNKNOWN: round(p[UNKNOWN], 3),
            "backward": r3(p["backward"]), "has_table": p["has_table"]}))
        for name, ms, scope in p["top_ops"]:
            print(f"  {ms:9.3f} ms  {scope or '-':40s} {name}")
        if p["has_table"]:
            print("  in no bucket: " + "; ".join(
                f"{name} {ms:.3f}" for name, ms, _ in p["top_residue"]))
    print("device seconds by scope, slice: " + json.dumps({
        k: round(table[k], 6)
        for k in ("busy_s", "scoped_s", "cut_s", "no_module_s")}),
        flush=True)


def program_scopes():
    """The program's own tables, or None where it hands out none."""
    try:
        from paddle_tpu.profiler import ledger
        return ledger.program_scopes()
    except (ImportError, AttributeError):
        return None


def table(ctx):
    """The run's table, made once and kept in ``ctx``; or None."""
    if "_program_scopes" not in ctx:
        ctx["_program_scopes"] = None
        scopes = program_scopes() if ctx.get("trace") else None
        path = trace_reduce.find_xplane(os.path.join(
            os.path.dirname(ctx["cell"]["bench_dir"]), ".cache",
            "benchmark_trace")) if scopes is not None else None
        if path:
            ctx["_program_scopes"] = reduce_profile(
                trace_reduce.load(path), scopes, load_buckets())
            report(ctx["_program_scopes"])
            # what the accepted readers of the same programs time, beside
            # it: every run on the XLA Modules line, the cut ones too
            print("run means on the XLA Modules line (ms): " + json.dumps(
                {n: round(1e3 * p["total_s"] / p["count"], 3) for n, p
                 in ctx["trace"].get("programs", {}).items()}), flush=True)
    return ctx["_program_scopes"]


def bucket_ms(ctx, program: str, bucket: str):
    """Mean device ms a run that ``program`` (a key of ``ctx["programs"]``:
    ``step``, ``chunk``) spends in ``bucket``; None without a table of
    that program."""
    t = table(ctx)
    p = t and t["programs"].get(ctx["programs"].get(program))
    if not p or not p["has_table"]:
        return None
    return p["buckets"].get(bucket, 0.0)


def scoped_pct(ctx):
    """Share of the slice's busy seconds (ops' self time inside the
    programs' runs) that fall in a bucket."""
    t = table(ctx)
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["scoped_s"] / t["busy_s"]


def main(argv) -> int:
    """Print the table of the capture and the ``program_scopes.json`` that
    ``paddle_tpu.profiler.Profiler`` left under ``argv[0]``."""
    trace_dir, = argv
    with open(os.path.join(trace_dir, "program_scopes.json")) as f:
        scopes = json.load(f)
    report(reduce_profile(
        trace_reduce.load(trace_reduce.find_xplane(trace_dir)), scopes,
        load_buckets()))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
