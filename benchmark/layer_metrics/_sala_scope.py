"""Device time under the scope components ``linear_attention`` (the matrix
state's recurrence: ``scan`` in a prefill chunk, ``update`` in a step) and
``sparse_attention`` (``pool``: the pooled keys a block completes, ``select``:
the pooled scores and the block choice, ``read``: the attention over the
span's valid columns under the chosen blocks' mask), by program and by
part.
``_program_scopes``' reduction of the run's capture, made once more with
these components listed, as ``_state_space_scope`` does with its own;
``scope_buckets.json`` is not touched (both lie inside ``attention``, where
the accepted ``step_attention_ms`` / ``chunk_attention_ms`` count them).
Beside them ``linear_attention/plane_copy``: the compiler's own copies of a
whole matrix-state plane between its memory spaces, which carry no scope
(``plane_copy_ms``).  None where the program hands out no scope tables, the run has no capture,
or the program has no such scope."""
from __future__ import annotations

import importlib
import json
import os

from benchmark import trace_reduce
from benchmark.layer_metrics import _program_scopes, _slot_loop

LINEAR, SPARSE = "linear_attention", "sparse_attention"
# scope component -> bucket: a part under the name of its mechanism
BUCKETS = {LINEAR: LINEAR, "scan": LINEAR + "/scan",
           "update": LINEAR + "/update", SPARSE: SPARSE,
           "pool": SPARSE + "/pool", "select": SPARSE + "/select",
           "read": SPARSE + "/read"}
PLANE_COPY = LINEAR + "/plane_copy"


def plane_copy_ms(profile, scopes, plane: str) -> dict:
    """{program: mean ms a run} in the compiler's own copies (``copy``,
    ``copy-start``, ``copy-done``) whose result is ``plane`` (``f32[24,32,
    128,128]``: a linear layer's state of every row) and whose scope names
    neither mechanism.  The compiled step has the update write each new
    plane into the chip's other memory space and a copy of its own take it
    from there to the program's output, so the time under
    ``linear_attention/update`` leaves that write out.  Nothing else of
    the program's residue is taken: what carries no scope and is not such
    a copy is another layer's.  The same whole runs as ``reduce_profile``
    counts."""
    spent = {}
    for modules, ops in _program_scopes._device_lines(profile):
        runs, _ = _program_scopes.lay(modules, ops)
        edge = _program_scopes._cut_runs(modules, runs)
        for k, (module, run) in enumerate(zip(modules, runs)):
            prog = trace_reduce._FINGERPRINT.sub("", module[2])
            if k in edge or prog not in scopes:
                continue
            n_s = spent.setdefault(prog, [0, 0.0])
            n_s[0] += 1
            for op, ns in run["ops"].items():
                short = trace_reduce.short_name(op).split(" ")
                entry = scopes[prog].get(_program_scopes.instruction_name(op))
                if short[0].startswith("%copy") and short[1:2] == [plane] \
                        and entry is not None and _program_scopes.bucket_of(
                            entry["scope"], BUCKETS) \
                        == _program_scopes.UNSCOPED:
                    n_s[1] += ns
    return {prog: s / 1e6 / n for prog, (n, s) in spent.items() if s}


def table(ctx):
    """The capture reduced under the components and their parts, kept in
    ``ctx`` and printed once."""
    if "_sala_scope" not in ctx:
        ctx["_sala_scope"] = None
        scopes = _program_scopes.program_scopes() if ctx.get("trace") \
            else None
        path = trace_reduce.find_xplane(os.path.join(
            os.path.dirname(ctx["cell"]["bench_dir"]), ".cache",
            "benchmark_trace")) if scopes is not None else None
        if path:
            profile = trace_reduce.load(path)
            t = _program_scopes.reduce_profile(profile, scopes, BUCKETS)
            plane = getattr(_counts(ctx), "linear_state_plane", None)
            for prog, spent in plane_copy_ms(
                    profile, scopes, plane(ctx["config"])).items() \
                    if plane else ():
                t["programs"][prog]["buckets"][PLANE_COPY] = spent
            ctx["_sala_scope"] = t
            print("device ms a run under linear_attention and "
                  "sparse_attention: " + json.dumps({
                      prog: {b: round(v, 3) for b, v in p["buckets"].items()}
                      for prog, p in t["programs"].items() if p["buckets"]}),
                  flush=True)
    return ctx["_sala_scope"]


def _counts(ctx):
    """``benchmark/counts/<family>.py``, or None."""
    try:
        return importlib.import_module(f"benchmark.counts.{ctx['family']}")
    except ImportError:
        return None


def ms(ctx, program: str, component: str, parts=None):
    """Mean device ms a run that ``program`` (``step``, ``chunk``) spends
    under ``component``, in ``parts`` of it (by default all that lies
    under the scope: the planes' copies only where ``parts`` names them); None
    without a table of that program or where nothing lies there."""
    t = table(ctx)
    p = t and t["programs"].get(ctx["programs"].get(program))
    if not p or not p["has_table"]:
        return None
    names = [b for b in p["buckets"] if b.split("/")[0] == component
             and (b != PLANE_COPY if parts is None
                  else b.partition("/")[2] in parts)]
    return sum(p["buckets"][b] for b in names) if names else None


def roofline_pct(ctx, program: str, component: str, parts, count: str,
                 per: str, counters):
    """100 x the least seconds ``benchmark/counts/<family>.py``'s ``count``
    gives for the window's average dispatch (each of ``counters`` of
    ``SlotLoop.counters`` a dispatch of ``per``) over the device time under
    ``component``'s ``parts`` in that program; None where the program
    keeps no such counter, the family has no such count or the trace no
    such scope."""
    c, peaks = _slot_loop.stats(ctx), ctx.get("peaks")
    spent = ms(ctx, program, component, parts)
    counts = _counts(ctx)
    if not spent or not peaks or not c.get(per) \
            or any(k not in c for k in counters) \
            or not hasattr(counts, count):
        return None
    least = getattr(counts, count)(ctx["config"],
                                   *(c[k] / c[per] for k in counters))
    least_s = max(least["bytes"] / peaks["hbm_bytes_per_s"],
                  least["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (spent / 1e3)
