"""Device time under the two parts of a delta-rule layer's chunked scan
(scope ``linear_attention/scan``): ``solve`` (the triangular system that
gives the pseudo-values: the scores ``K K^T``, the inverse and its two
right-hand sides) and ``carry`` (the chunks' own sums and the state passed
from one to the next), by program.  ``_program_scopes``' reduction of the
run's capture, made once more with these two components listed, as
``_state_space_scope`` and ``_sala_scope`` do with theirs
(``_sala_scope.BUCKETS`` lists neither, so the accepted readers go on
counting both under ``linear_attention/scan``).  None where the program
hands out no scope tables, the run has no capture, or the program names no
such scope (a lightning layer's scan has no system to solve)."""
from __future__ import annotations

import json
import os

from benchmark import trace_reduce
from benchmark.layer_metrics import _program_scopes

PARTS = ("solve", "carry")


def table(ctx):
    """The capture reduced under the two parts, kept in ``ctx`` and
    printed once."""
    if "_delta_scope" not in ctx:
        ctx["_delta_scope"] = None
        scopes = _program_scopes.program_scopes() if ctx.get("trace") \
            else None
        path = trace_reduce.find_xplane(os.path.join(
            os.path.dirname(ctx["cell"]["bench_dir"]), ".cache",
            "benchmark_trace")) if scopes is not None else None
        if path:
            t = _program_scopes.reduce_profile(
                trace_reduce.load(path), scopes, {c: c for c in PARTS})
            ctx["_delta_scope"] = t
            print("device ms a run under the delta rule's scan: "
                  + json.dumps({
                      prog: {b: round(v, 3) for b, v in p["buckets"].items()
                             if b in PARTS}
                      for prog, p in t["programs"].items()
                      if any(b in PARTS for b in p["buckets"])}), flush=True)
    return ctx["_delta_scope"]


def ms(ctx, program: str, part: str):
    """Mean device ms a run that ``program`` (``step``, ``chunk``) spends
    under ``part``; None without a table of that program or where nothing
    lies under that name."""
    t = table(ctx)
    p = t and t["programs"].get(ctx["programs"].get(program))
    if not p or not p["has_table"]:
        return None
    return p["buckets"].get(part) or None
