"""What the readers of the slot loop's own measurement share: the loop's
``stats()`` as the runner handed it over (``ctx["counters"]["slot_loop"]``:
``phases_ms``, ``phase_s`` and the ``slot_steps_*`` counters of
``paddle_tpu/serving/slots.py``), and the traced slice's idle seconds laid
under the driver thread's spans.  Every function returns None where the
program has no such field, span or capture."""
from __future__ import annotations

import json
import os

from benchmark import harness, trace_reduce


def stats(ctx) -> dict:
    return (ctx.get("counters") or {}).get("slot_loop") or {}


def phase_p90_ms(ctx, phase: str):
    """90th percentile of one phase of a request's life, over the requests
    replied in the window."""
    ph = (stats(ctx).get("phases_ms") or {}).get(phase)
    return ph["p90"] if ph else None


def slot_steps_pct(ctx, state: str):
    """Share of the window's slot-steps (steps x slots) spent in ``state``."""
    c = stats(ctx)
    n = c.get("slot_steps_" + state)
    if n is None or not c.get("steps"):
        return None
    return 100.0 * n / (c["steps"] * c["slots"])


def driver_span_names():
    try:
        from paddle_tpu.serving import slots
        return tuple(slots.SPAN_NAMES)
    except (ImportError, AttributeError):
        return None


def idle_by_span(path: str, names):
    """{label: idle seconds} of the capture at ``path``: each gap of the
    device under the span of ``names`` that covers most of it, "no span"
    where none does; None without a device plane."""
    reduced = trace_reduce.reduce_profile(trace_reduce.load(path),
                                          tuple(names))
    return None if reduced is None else dict(reduced["idle_gaps"])


def idle_by_driver_span(ctx):
    """``idle_by_span`` of the run's traced slice under the driver thread's
    spans, printed as one line; or None.  The capture is where
    ``harness.ProfilerSlice`` put it; it is reduced a second time here,
    with the program's own span names, because the run's first reduction
    knows the harness's only."""
    names = driver_span_names()
    if not names or not ctx.get("trace"):
        return None
    path = trace_reduce.find_xplane(os.path.join(
        os.path.dirname(ctx["cell"]["bench_dir"]), ".cache",
        "benchmark_trace"))
    gaps = idle_by_span(path, names) if path else None
    if gaps is not None:
        print("idle seconds by driver span: " + json.dumps(
            {k: round(v, 6) for k, v in gaps.items()}), flush=True)
        _print_loop(ctx)
    return gaps


def _print_loop(ctx):
    """The loop's own measurement over the window, whole, beside the idle
    line: the driver's seconds by phase, the slot-step split, and the
    requests' phases next to the client's own median (PERF.md section 5
    is written from these lines)."""
    c = stats(ctx)
    served = sorted((ctx.get("counters") or {}).get("served_latency_ms") or [])
    print("driver seconds by phase: " + json.dumps(
        {k: round(v, 6) for k, v in c.get("phase_s", {}).items()}))
    print("slot-steps: " + json.dumps(
        {k: v for k, v in c.items() if k.startswith("slot_steps_")
         or k in ("steps", "slots", "chunks", "session_resets")}))
    print("request phases (ms): " + json.dumps(
        {k: {a: round(b, 3) for a, b in v.items()}
         for k, v in c.get("phases_ms", {}).items()})
        + f"; served by the client's clock: n {len(served)}, median "
        f"{harness.percentile(served, 50) if served else float('nan'):.3f}",
        flush=True)
