"""Prefill chunk dispatches per decode step over the window
(``chunks / steps`` of ``SlotLoop.counters``)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if not c.get("steps") or "chunks" not in c:
        return None
    return c["chunks"] / c["steps"]
