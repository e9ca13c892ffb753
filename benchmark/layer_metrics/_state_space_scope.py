"""Device time under the scope component ``state_space`` (a state-space
layer with its norm, projections and residual add), by program and by
part: ``conv`` (the causal convolution and its inputs), ``scan`` (the
chunked scan of a prefill chunk), ``update`` (the one-token update of a
step), and ``state_space`` itself for what lies directly under it (the
projections, the gate and group norm).  ``_program_scopes``' reduction of
the run's capture, made once more with these components listed, as
``_selector_scope`` does with its one; ``scope_buckets.json`` is not
touched (it lists no ``state_space``, so the accepted ``device_scoped_pct``
counts these layers as unscoped).  None where the program hands out no
scope tables, the run has no capture, or the program has no table."""
from __future__ import annotations

import importlib
import json
import os

from benchmark import trace_reduce
from benchmark.layer_metrics import _program_scopes, _slot_loop

COMPONENT = "state_space"
PARTS = ("conv", "scan", "update")


def table(ctx):
    """The capture reduced under the component and its parts, kept in
    ``ctx`` and printed once."""
    if "_state_space_scope" not in ctx:
        ctx["_state_space_scope"] = None
        scopes = _program_scopes.program_scopes() if ctx.get("trace") \
            else None
        path = trace_reduce.find_xplane(os.path.join(
            os.path.dirname(ctx["cell"]["bench_dir"]), ".cache",
            "benchmark_trace")) if scopes is not None else None
        if path:
            t = _program_scopes.reduce_profile(
                trace_reduce.load(path), scopes,
                {c: c for c in (COMPONENT,) + PARTS})
            ctx["_state_space_scope"] = t
            print("device ms a run under state_space: " + json.dumps({
                prog: {b: round(v, 3) for b, v in p["buckets"].items()}
                for prog, p in t["programs"].items() if p["buckets"]}),
                flush=True)
    return ctx["_state_space_scope"]


def ms(ctx, program: str, parts=(COMPONENT,) + PARTS):
    """Mean device ms a run that ``program`` (``step``, ``chunk``) spends
    under the component, in ``parts`` (all of it by default); None without
    a table of that program or where nothing lies under the component."""
    t = table(ctx)
    p = t and t["programs"].get(ctx["programs"].get(program))
    if not p or not p["has_table"] or not p["buckets"]:
        return None
    return sum(p["buckets"].get(b, 0.0) for b in parts)


def roofline_pct(ctx, program: str, part: str, count: str, per: str,
                 counter: str):
    """100 x the least seconds ``benchmark/counts/<family>.py``'s
    ``count`` gives for the window's average dispatch (``counter`` of
    ``SlotLoop.counters`` a dispatch of ``per``: the live rows a step
    updated, the valid tokens a chunk scanned) over the device time under
    ``state_space/<part>`` of that program; None where the program keeps no
    such counter, the family has no such count or the trace no such
    scope."""
    c, peaks = _slot_loop.stats(ctx), ctx.get("peaks")
    spent = ms(ctx, program, (part,))
    if not spent or not peaks or not c.get(per) or counter not in c:
        return None
    try:
        counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
    except ImportError:
        return None
    if not hasattr(counts, count):
        return None
    least = getattr(counts, count)(ctx["config"], c[counter] / c[per])
    least_s = max(least["bytes"] / peaks["hbm_bytes_per_s"],
                  least["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (spent / 1e3)
