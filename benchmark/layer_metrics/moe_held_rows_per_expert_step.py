"""Rows an expert HELD on this chip computes in a decode step, on average:
the steps' held assignments / steps / expert layers / experts held
(``SlotLoop.counters``; the layers and the experts held from
``benchmark/counts/<family>.py``'s ``layers`` and ``held_experts``, so a
family needs no config key of another's).  Below ~240 rows (peak FLOP/s
over peak bytes/s) an expert's products cost less than streaming its
weights.  None where the program keeps no such counters or the family's
counts have no such functions."""
import importlib

from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if not c.get("steps") or "chunk_moe_assignments_held" not in c:
        return None
    try:
        counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
        n = counts.layers(ctx["config"], "E") \
            * counts.held_experts(ctx["config"])
    except (ImportError, AttributeError):
        return None
    return (c["moe_assignments_held"] - c["chunk_moe_assignments_held"]) \
        / c["steps"] / n
