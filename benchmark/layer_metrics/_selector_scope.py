"""Device time under the scope component ``selector`` (the learned column
selector of the latent family's full layers: its projections, its scores
``selector/score`` and the search among them ``selector/select``), by
program.  ``_program_scopes``' reduction of the run's capture, made once
more with ONE component listed, so that every op whose scope path holds
``selector`` falls into one bucket of that name; ``scope_buckets.json``
is not touched, and what is read here is a PART of the ``attention``
bucket that ``step_attention_ms`` / ``chunk_attention_ms`` read.  None
where the program hands out no scope tables, the run has no capture, or
the program has no table."""
from __future__ import annotations

import os

from benchmark import trace_reduce
from benchmark.layer_metrics import _program_scopes

COMPONENT = "selector"


def table(ctx):
    """The capture reduced under the one component, kept in ``ctx``."""
    if "_selector_scope" not in ctx:
        ctx["_selector_scope"] = None
        scopes = _program_scopes.program_scopes() if ctx.get("trace") \
            else None
        path = trace_reduce.find_xplane(os.path.join(
            os.path.dirname(ctx["cell"]["bench_dir"]), ".cache",
            "benchmark_trace")) if scopes is not None else None
        if path:
            ctx["_selector_scope"] = _program_scopes.reduce_profile(
                trace_reduce.load(path), scopes, {COMPONENT: COMPONENT})
    return ctx["_selector_scope"]


def ms(ctx, program: str):
    """Mean device ms a run that ``program`` (``step``, ``chunk``) spends
    under the component; None without a table of that program."""
    t = table(ctx)
    p = t and t["programs"].get(ctx["programs"].get(program))
    if not p or not p["has_table"]:
        return None
    return p["buckets"].get(COMPONENT, 0.0)
